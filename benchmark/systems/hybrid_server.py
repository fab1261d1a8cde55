"""The system under test for the `phi4flash` family: `TokenServer` over
the paged engine, serving the program's `Phi4Flash`.

Everything the harness reads of a running server (its address, stats,
request lifecycle, annotations, stop, free) and the wire client are
`token_server.py`'s; this file brings what differs: it turns the
benchmark's own weights (`reference/phi4flash.py`) into the program's
model through the program's own constructors (`Phi4Flash.make_layer`,
`Phi4Flash.build`), a layer at a time.
"""

from __future__ import annotations

import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.reference import phi4flash as ref
from benchmark.systems import token_server as base
from benchmark.systems.token_server import (IdTokenizer,  # noqa: F401
                                            prompt_text, request)


def _model_config(cfg: dict):
    from triton_dist_tpu.models.phi4flash import Phi4FlashConfig
    a = cfg["assumed"]
    return Phi4FlashConfig(
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        vocab_size=cfg["vocab_size"],
        sliding_window=cfg["sliding_window"],
        layer_norm_eps=cfg["layer_norm_eps"],
        d_state=a["mamba_d_state"], d_conv=a["mamba_d_conv"],
        expand=a["mamba_expand"], dt_rank=a["mamba_dt_rank"],
        dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int, devices):
    """The program's `Phi4Flash` on `devices[0]`, holding the
    benchmark's weights for `seed`."""
    from triton_dist_tpu.models.phi4flash import Phi4Flash
    from triton_dist_tpu.runtime import initialize_distributed

    if len(devices) != 1:
        raise ValueError("the phi4flash family serves on one chip")
    ctx = initialize_distributed({"tp": 1}, devices=devices)
    mesh = ctx.mesh
    mc = _model_config(cfg)
    rep = NamedSharding(mesh, P())
    head = ref.head_weights(cfg, seed, rep)
    fns = {}
    layers = []
    for li in range(mc.num_layers):
        kind = ref.layer_kind(cfg, li)
        assert kind == mc.kind(li)
        if kind not in fns:
            fns[kind] = ref.layer_weights_fn(cfg, kind, rep)
        layers.append(Phi4Flash.make_layer(
            mc, li, fns[kind](ref.layer_key(seed, li)), mesh))
    return Phi4Flash.build(mc, head, layers, mesh)


class Served(base.Served):
    """`token_server.Served` with this family's model under it."""

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
