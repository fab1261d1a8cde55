"""The system under test for the `afmoe` family: `TokenServer` over the
paged engine, serving the program's `Afmoe` as the one chip's share the
configuration states.

Everything the harness reads of a running server and the wire client
are `token_server.py`'s; this file brings what differs: it turns the
benchmark's own weights (`reference/afmoe.py`: the dense layer whole, of
an expert layer the held experts only) into the program's model through
the program's own constructors (`Afmoe.make_layer`, `Afmoe.build`), a
layer at a time.
"""

from __future__ import annotations

import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.reference import afmoe as ref
from benchmark.systems import token_server as base
from benchmark.systems.token_server import (IdTokenizer,  # noqa: F401
                                            prompt_text, request)


def _model_config(cfg: dict):
    from triton_dist_tpu.models.afmoe import AfmoeConfig
    s = ref.sizes(cfg)
    return AfmoeConfig(
        hidden_size=s["D"], intermediate_size=s["I"],
        moe_intermediate_size=s["F"], num_layers=s["L"],
        num_dense_layers=s["dense"], num_heads=s["Hq"],
        num_kv_heads=s["Hkv"], head_dim=s["hd"], sliding_window=s["W"],
        global_attn_every_n_layers=s["every"], n_routed_experts=s["E"],
        num_shared_experts=s["shared"], num_experts_per_tok=s["k"],
        route_scale=s["route_scale"], held_first=s["first"],
        held_count=s["held"], vocab_size=s["V"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=s["theta"], rms_norm_eps=s["eps"],
        dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int, devices):
    """The program's `Afmoe` on `devices[0]`, holding the benchmark's
    weights for `seed`."""
    from triton_dist_tpu.models.afmoe import Afmoe
    from triton_dist_tpu.runtime import initialize_distributed

    if len(devices) != 1:
        raise ValueError("the afmoe family serves one chip's share")
    ctx = initialize_distributed({"tp": 1}, devices=devices)
    mesh = ctx.mesh
    mc = _model_config(cfg)
    rep = NamedSharding(mesh, P())
    head = ref.head_weights(cfg, seed, rep)
    fns = {}
    layers = []
    for li in range(mc.num_layers):
        kind = ref.layer_kind(cfg, li)
        # the file's `layer_types` and the program's rule agree
        assert kind == mc.kind(li), (li, kind, mc.kind(li))
        if kind[1] not in fns:
            fns[kind[1]] = ref.layer_weights_fn(cfg, kind[1], rep)
        layers.append(Afmoe.make_layer(
            mc, li, fns[kind[1]](ref.layer_key(seed, li)), mesh))
    return Afmoe.build(mc, head, layers, mesh)


class Served(base.Served):
    """`token_server.Served` with this family's model under it (the
    construction is `deepseek_server.Served`'s, which names its own
    `build_model`)."""

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
