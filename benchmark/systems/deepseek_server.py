"""The system under test for the `deepseek_v3` family: `TokenServer`
over the paged engine, serving the program's `DeepSeekV3` as the one
chip's share the configuration states.

Everything the harness reads of a running server and the wire client
are `token_server.py`'s; this file brings what differs: it turns the benchmark's own weights
(`reference/deepseek_v3.py`: the dense layers whole, of an expert layer
the held experts only) into the program's model through the program's
own constructors (`DeepSeekV3.make_layer`, `DeepSeekV3.build`), a layer
at a time.
"""

from __future__ import annotations

import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.reference import deepseek_v3 as ref
from benchmark.systems import token_server as base
from benchmark.systems.token_server import (IdTokenizer,  # noqa: F401
                                            prompt_text, request)


def _model_config(cfg: dict):
    from triton_dist_tpu.models.deepseek import DeepSeekConfig
    s, rs = ref.sizes(cfg), cfg["rope_scaling"]
    return DeepSeekConfig(
        hidden_size=s["D"], intermediate_size=s["I"],
        moe_intermediate_size=s["F"], num_layers=s["L"],
        first_k_dense_replace=s["dense"], num_heads=s["H"],
        q_lora_rank=s["Rq"], kv_lora_rank=s["Rkv"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
        v_head_dim=s["vd"], n_routed_experts=s["E"],
        n_shared_experts=s["shared"], num_experts_per_tok=s["k"],
        n_group=s["groups"], topk_group=s["topk_group"],
        routed_scaling_factor=s["route_scale"], held_first=s["first"],
        held_count=s["held"], vocab_size=s["V"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=s["theta"], rope_factor=rs["factor"],
        rope_original_max=rs["original_max_position_embeddings"],
        rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
        rope_mscale=rs["mscale"], rope_mscale_all_dim=rs["mscale_all_dim"],
        rms_norm_eps=s["eps"], dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int, devices):
    """The program's `DeepSeekV3` on `devices[0]`, holding the
    benchmark's weights for `seed`."""
    from triton_dist_tpu.models.deepseek import DeepSeekV3
    from triton_dist_tpu.runtime import initialize_distributed

    if len(devices) != 1:
        raise ValueError("the deepseek_v3 family serves one chip's share")
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
        layers.append(DeepSeekV3.make_layer(
            mc, li, fns[kind](ref.layer_key(seed, li)), mesh))
    return DeepSeekV3.build(mc, head, layers, mesh)


class Served(base.Served):
    """`token_server.Served` with this family's model under it (the
    construction is `hybrid_server.Served`'s, which names its own
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
