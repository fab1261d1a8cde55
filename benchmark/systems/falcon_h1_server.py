"""The system under test for the `falcon_h1` family: `TokenServer` over
the paged engine, serving the program's `FalconH1` as the one-chip
pipeline stage the configuration states.

Everything the harness reads of a running server and the wire client
are `token_server.py`'s; this file brings what differs: it turns the
benchmark's own weights (`reference/falcon_h1.py`, the published tensors
with no multiplier applied) into the program's model through the
program's own constructors (`FalconH1.make_layer`, `FalconH1.build`), a
layer at a time.

The program's model module is imported with this file, before any
weight is made: a program without it fails the cell at once.
"""

from __future__ import annotations

import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.reference import falcon_h1 as ref
from benchmark.systems import token_server as base
from benchmark.systems.token_server import (IdTokenizer,  # noqa: F401
                                            prompt_text, request)
from triton_dist_tpu.models import falcon_h1 as program


def _model_config(cfg: dict):
    s = ref.sizes(cfg)
    return program.FalconH1Config(
        hidden_size=s["D"], intermediate_size=s["I"], num_layers=s["L"],
        num_heads=s["Hq"], num_kv_heads=s["Hkv"], head_dim=s["hd"],
        mamba_n_heads=s["H"], mamba_d_head=s["P"], mamba_d_state=s["N"],
        mamba_n_groups=s["G"], mamba_d_conv=s["K"],
        mamba_chunk_size=int(cfg["mamba_chunk_size"]),
        vocab_size=s["V"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=s["theta"], rms_norm_eps=s["eps"],
        embedding_multiplier=s["emb"], lm_head_multiplier=s["lm"],
        attention_in_multiplier=s["a_in"],
        attention_out_multiplier=s["a_out"], key_multiplier=s["key"],
        ssm_in_multiplier=s["s_in"], ssm_out_multiplier=s["s_out"],
        ssm_multipliers=s["mup"], mlp_multipliers=s["mlp"],
        dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int, devices):
    """The program's `FalconH1` on `devices[0]`, holding the benchmark's
    weights for `seed`."""
    from triton_dist_tpu.runtime import initialize_distributed

    if len(devices) != 1:
        raise ValueError("the falcon_h1 family serves one chip")
    ctx = initialize_distributed({"tp": 1}, devices=devices)
    mesh = ctx.mesh
    mc = _model_config(cfg)
    rep = NamedSharding(mesh, P())
    head = ref.head_weights(cfg, seed, rep)
    fn = ref.layer_weights_fn(cfg, rep)
    layers = [program.FalconH1.make_layer(mc, fn(ref.layer_key(seed, li)),
                                          mesh)
              for li in range(mc.num_layers)]
    return program.FalconH1.build(mc, head, layers, mesh)


class Served(base.Served):
    """`token_server.Served` with this family's model under it (the
    construction is `afmoe_server.Served`'s, which names its own
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
