"""The system under test: `TokenServer` over the paged engine.

The one file of the benchmark that imports the program. It turns the
benchmark's own weights into the program's model through the program's
own constructors (`TP_Attn.init`, `TP_MLP.init`, `DenseLLM`), builds the
`Engine` and the `TokenServer` the configuration's file asks for, and
hands back what the harness needs: the server's address, its `stats()`,
its request lifecycle, and a way to stop it. Nothing here computes a
metric or a reference.
"""

from __future__ import annotations

import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.reference import qwen3 as ref


class IdTokenizer:
    """Token ids over the whole vocabulary through the text wire: the
    prompt is the ids in decimal, separated by spaces. (The program's
    ByteTokenizer reaches ids below 256 only.)"""

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str):
        ids = [int(t) for t in text.split()]
        if any(not 0 <= i < self.vocab_size for i in ids):
            raise ValueError("token id outside the vocabulary")
        return ids

    def decode(self, ids):
        return prompt_text(ids)


def prompt_text(ids) -> str:
    return " ".join(str(int(i)) for i in ids)


def _model_config(cfg: dict):
    from triton_dist_tpu.models.config import ModelConfig
    return ModelConfig(
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], vocab_size=cfg["vocab_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        model_type=cfg["model_type"], dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int, devices):
    """The program's `DenseLLM` over a TP mesh of `devices`, holding the
    benchmark's weights for `seed`. Each layer's leaves are made on the
    device in one jitted call, already split the way TP splits them, so
    no chip holds a whole layer."""
    from triton_dist_tpu.layers import TP_Attn, TP_MLP, precompute_rope
    from triton_dist_tpu.models.dense import DenseLayer, DenseLLM
    from triton_dist_tpu.models.utils import place_replicated
    from triton_dist_tpu.runtime import initialize_distributed

    ctx = initialize_distributed({"tp": len(devices)}, devices=devices)
    mesh = ctx.mesh
    mc = _model_config(cfg)
    rep = NamedSharding(mesh, P())

    def split(name):
        ax = ref.TP_SPLIT_AXIS.get(name)
        if ax is None:
            return rep
        return NamedSharding(mesh, P("tp", None) if ax == 0
                             else P(None, "tp"))

    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
             "ln_attn", "ln_mlp", "q_norm", "k_norm")
    lw_fn = ref.layer_weights_fn(cfg, {n: split(n) for n in names})
    # the replicated embedding and head first, while the chips are empty
    hw = ref.head_weights_fn(cfg, rep)(ref.head_key(seed))
    layers = []
    for li in range(mc.num_layers):
        w = lw_fn(ref.layer_key(seed, li))
        attn = TP_Attn.init(
            w["wq"], w["wk"], w["wv"], w["wo"], mesh=mesh, axis="tp",
            n_heads=mc.num_heads, n_kv_heads=mc.num_kv_heads,
            head_dim=mc.head_dim, q_norm=w["q_norm"], k_norm=w["k_norm"])
        mlp = TP_MLP.init(w["w_gate"], w["w_up"], w["w_down"], mesh=mesh,
                          axis="tp")
        layers.append(DenseLayer(attn=attn, mlp=mlp, ln_attn=w["ln_attn"],
                                 ln_mlp=w["ln_mlp"]))
    cos, sin = precompute_rope(mc.head_dim, mc.max_position_embeddings,
                               mc.rope_theta)
    model = DenseLLM(
        embed=hw["embed"], layers=tuple(layers),
        final_norm=hw["final_norm"],
        lm_head=(hw["embed"].T if mc.tie_word_embeddings
                 else hw["lm_head"]),
        cos=cos, sin=sin, config=mc, mesh=mesh, axis="tp")
    return place_replicated(model, mesh)


class _AnnotatedSocket:
    """The listening socket with its `accept` (which blocks up to the
    serve loop's 20 ms timeout every iteration) on the profiler's
    clock. A socket takes no new attribute, hence the proxy."""

    def __init__(self, sock):
        self._sock = sock

    def accept(self):
        with jax.profiler.TraceAnnotation("bench:accept_wait"):
            return self._sock.accept()

    def __getattr__(self, name):
        return getattr(self._sock, name)


class Served:
    """A running `TokenServer` and the handles the harness reads."""

    def __init__(self, cfg: dict, seed: int, devices, *, trace: bool):
        from triton_dist_tpu.models import Engine
        from triton_dist_tpu.serving import TokenServer
        eng_opt, srv_opt = cfg["engine"], cfg["server"]
        self.model = build_model(cfg, seed, devices)
        jax.block_until_ready(jax.tree.leaves(self.model))
        self.weight_bytes = sum(
            x.nbytes for x in jax.tree.leaves(self.model)
            if hasattr(x, "nbytes"))
        self.engine = Engine(self.model, max_seq=eng_opt["max_seq"],
                             backend=eng_opt["backend"])
        self.batch = srv_opt["batch"]
        self.chunk = srv_opt.get("chunk", 4)
        self.srv = TokenServer(
            self.engine, IdTokenizer(cfg["vocab_size"]),
            batch=self.batch, chunk=self.chunk, paged=srv_opt["paged"],
            prefix_cache=srv_opt["prefix_cache"], page=srv_opt["page"],
            trace=trace)
        self.host, self.port = self.srv.host, self.srv.port
        self.errors: list = []
        self._thread = threading.Thread(target=self._serve,
                                        name="bench-server")
        self._thread.start()

    def _serve(self):
        try:
            self.srv.serve_forever()
        except BaseException as e:        # surfaced by the harness
            self.errors.append(e)

    def stats(self) -> dict:
        """The scheduler's registry snapshot plus the engine's
        process-global dispatch counters, one flat dict of numbers."""
        from triton_dist_tpu.runtime.telemetry import default_registry
        st = dict(self.srv.stats())
        for k, v in default_registry().snapshot().items():
            st.setdefault(k, v)
        return st

    def lifecycle(self) -> dict:
        """Per-request event lists ([ms, name, detail]) of the traced
        scheduler; empty when it was built with trace off."""
        return self.srv.sched.tele.export()["requests"]

    def annotate(self) -> None:
        """Traced run only: put the scheduler's phases on the profiler's
        clock, from outside, by wrapping the bound methods the poll loop
        calls in `TraceAnnotation`s named `bench:<phase>`. (The program's
        own timeline is on the host's monotonic clock; giving it
        annotations of its own is the `tracing` issue's.)"""
        sched, srv = self.srv.sched, self.srv

        def wrap(obj, attr, name):
            inner = getattr(obj, attr)

            def outer(*a, **kw):
                with jax.profiler.TraceAnnotation("bench:" + name):
                    return inner(*a, **kw)
            setattr(obj, attr, outer)

        wrap(sched, "poll", "poll")
        wrap(sched, "_admit", "admit")
        wrap(sched.slots, "step_chunk", "decode_chunk")
        wrap(sched.slots, "_fetch", "device_wait")
        wrap(srv, "_emit", "wire_write")
        wrap(srv, "_probe_disconnects", "probe_disconnects")
        wrap(srv._stop, "wait", "idle_sleep")
        srv._sock = _AnnotatedSocket(srv._sock)

    def pool_pages(self) -> int:
        return int(self.srv.sched.slots.prefix.pool.num_pages)

    def stop(self, timeout: float = 60.0) -> None:
        self.srv.stop()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            raise RuntimeError("the server thread did not stop")

    def free(self) -> None:
        """Drop every device buffer of the program, so that the
        reference runs beside nothing."""
        self.srv = self.engine = self.model = None
        import gc
        gc.collect()


def request(host: str, port: int, ids, gen_len: int, timeout: float):
    """One request over the program's own client; yields the server's
    messages."""
    from triton_dist_tpu.serving import request_stream
    return request_stream(host, port, prompt_text(ids), gen_len=gen_len,
                          timeout=timeout, busy_retries=0)
