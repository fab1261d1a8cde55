"""Model-side utilities (reference: `python/triton_dist/models/utils.py`
— sampling helpers + emoji logger)."""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp

logger = logging.getLogger("triton_dist_tpu")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[tdtpu] %(levelname)s %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)


@dataclasses.dataclass(frozen=True)
class ServingTraits:
    """What a model tells the Engine and the scheduler about itself, so
    that neither reaches into `model.layers[0].attn` or assumes one kind
    of layer (every model class has a `serving_traits()`).

    kv_heads: the page-table rows one slot holds, i.e. the heads of the
    paged pool (a model may pool heads in pairs);
    slot_state: the capability name of state a slot holds BESIDE its
    pages and that pages cannot express (recurrent state, a window
    ring) — None for a model whose whole context is its pages. A model
    with slot_state admits through its own `admit_slot_paged`, and
    every option that rebuilds a slot from pages alone is refused;
    own_pool: the name of a paged pool whose layout only the model's
    own programs read and write (a latent pool: one plane, no V) —
    None for K and V planes, which the Engine's admission, copy-on-
    write, gather and restore programs move. A slot of such a model IS
    its pages, but until those programs learn the layout it admits
    through its own `admit_slot_paged` too, and the same options are
    refused, by the pool's name."""
    kv_heads: int
    slot_state: str | None = None
    own_pool: str | None = None


# (name, help, labels) of the two entries a share's routing-load vector
# ends with where the model counts them (a config's `load_counters`)
EXPERTS_TOUCHED_COUNTERS = (
    ("moe_experts_touched",
     "held experts that a decode step's pairs reached (whose weights "
     "the grouped GEMM read), over steps and layers", None),
    ("moe_experts_offered",
     "held experts, over the same steps and layers", None))


def place_replicated(tree, mesh):
    """Place `tree` over `mesh`: the one placement point at a model's
    boundary (every constructor, and a caller that builds a model from
    its own weights, ends with it). Every array not yet placed over
    the mesh is committed to it REPLICATED: the TP layers shard their
    own projections; what a constructor makes beside them (embedding,
    norms, rope tables) is otherwise an uncommitted array on the first
    device — one chip holds it all, and every program call copies it
    out again to the others. The leaves a model names in
    `split_leaves()` ({field: mesh axis}; DenseLLM's LM head) are
    instead SPLIT along their last dimension over that axis, also
    where they arrive replicated over the mesh: each chip keeps its
    own columns, nothing moves between chips, and the replicated copy
    is freed with the caller's last reference to it. (The name is from
    before a leaf was split, and stays because callers import it.
    Under a trace, as in jax.eval_shape, there is nothing to place.)"""
    import dataclasses

    def put(x, axis=None):
        if not isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer):
            return x
        if axis is not None or getattr(x.sharding, "mesh", None) != mesh:
            return jax.device_put(x, last_axis_sharding(mesh, x.ndim, axis))
        return x

    split = getattr(tree, "split_leaves", dict)()
    if split:
        tree = dataclasses.replace(tree, **{
            field: jax.tree.map(lambda x, ax=ax: put(x, ax),
                                getattr(tree, field))
            for field, ax in split.items()})
    return jax.tree.map(put, tree)


def last_axis_sharding(mesh, ndim: int, axis):
    """An ndim-array over `mesh` with its LAST dimension split over
    mesh axis `axis` and every other replicated (None: all of it
    replicated, under the empty spec a program's replicated results
    carry: P(None, None) is another sharding to jit's cache)."""
    from jax.sharding import NamedSharding, PartitionSpec
    if axis is None:
        return NamedSharding(mesh, PartitionSpec())
    return NamedSharding(mesh, PartitionSpec(*[None] * (ndim - 1), axis))


def split_last_axis(x, mesh, axis):
    """Inside a program: pin x's LAST dimension split over mesh axis
    `axis`, every other replicated (None: x as it is). What keeps
    [.., V] logits on the columns of a vocabulary-split LM head
    (DenseLLM.vocab_axis) by statement and not by what the partitioner
    happens to propagate."""
    if axis is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, last_axis_sharding(mesh, x.ndim, axis))


def sample_greedy(logits):
    return jnp.argmax(logits, axis=-1)


def top_k_support(logits, k: int, temperature: float):
    """Temperature-scaled logits restricted to the top-k support:
    (values [..., k], vocab indices [..., k]). SHARED by sample_top_k
    and the speculative-verify target distribution
    (models/spec_decode.py target_probs) — the leftover rejection
    sampling is exact only if both draw from the same support."""
    return jax.lax.top_k(logits / temperature, k)


def top_p_masked_logits(logits, p: float, temperature: float):
    """Temperature-scaled logits with the nucleus tail (cumulative
    prob > p) masked to -inf. SHARED by sample_top_p and the
    speculative-verify target distribution (same exactness contract as
    top_k_support)."""
    logits = logits / temperature
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < p, axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


def sample_top_k(key, logits, k: int = 50, temperature: float = 1.0):
    """Top-k sampling (reference: models/utils.py sampling helpers)."""
    topv, topi = top_k_support(logits, k, temperature)
    idx = jax.random.categorical(key, topv)
    return jnp.take_along_axis(topi, idx[..., None], axis=-1)[..., 0]


def sample_top_p(key, logits, p: float = 0.9, temperature: float = 1.0):
    """Nucleus sampling: mask the tail whose cumulative prob > p."""
    return jax.random.categorical(
        key, top_p_masked_logits(logits, p, temperature))
