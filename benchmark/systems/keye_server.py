"""The system under test for the `keye_vl2` family: `TokenServer` over
the paged engine, serving the program's `Qwen3MoE` with `sa_config` as
the one chip's share the configuration states.

Everything the harness reads of a running server and the wire client
are `token_server.py`'s; this file brings what differs: it turns the
benchmark's own weights (`reference/keye_vl2.py`: of every layer the
held experts only) into the program's model through the program's own
constructors (`Qwen3MoE.make_sa_layer`, `Qwen3MoE.build_sa`), a layer at
a time.
"""

from __future__ import annotations

import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.reference import keye_vl2 as ref
from benchmark.systems import token_server as base
from benchmark.systems.token_server import (IdTokenizer,  # noqa: F401
                                            prompt_text, request)


def _model_config(cfg: dict):
    from triton_dist_tpu.models.config import ModelConfig, SAConfig
    s = ref.sizes(cfg)
    return ModelConfig(
        hidden_size=s["D"], intermediate_size=cfg["intermediate_size"],
        num_layers=s["L"], num_heads=s["Hq"], num_kv_heads=s["Hkv"],
        head_dim=s["hd"], vocab_size=s["V"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=s["theta"], rms_norm_eps=s["eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        model_type=cfg["model_type"], num_experts=s["E"],
        num_experts_per_tok=s["k"], moe_intermediate_size=s["F"],
        dtype=cfg["torch_dtype"],
        sa_config=SAConfig(indexer_num_heads=s["Hi"],
                           indexer_head_dim=s["di"], topk=s["topk"]),
        mrope_section=s["sections"],
        held_experts=(s["first"], s["held"]))


def build_model(cfg: dict, seed: int, devices):
    """The program's `Qwen3MoE` on `devices[0]`, holding the benchmark's
    weights for `seed`."""
    from triton_dist_tpu.models.qwen_moe import Qwen3MoE
    from triton_dist_tpu.runtime import initialize_distributed

    if len(devices) != 1:
        raise ValueError("the keye_vl2 family serves one chip's share")
    ctx = initialize_distributed({"tp": 1}, devices=devices)
    mesh = ctx.mesh
    mc = _model_config(cfg)
    rep = NamedSharding(mesh, P())
    head = ref.head_weights(cfg, seed, rep)
    fn = ref.layer_weights_fn(cfg, rep)
    layers = [Qwen3MoE.make_sa_layer(mc, fn(ref.layer_key(seed, li)), mesh)
              for li in range(mc.num_layers)]
    return Qwen3MoE.build_sa(mc, head, layers, mesh)


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
