"""Expert parallelism: a mixture-of-experts layer sharded over an
``expert`` mesh axis.

No reference behavior to match (SURVEY.md section 2.6 item 4); native
capability.  Design: expert parameters carry a leading expert dim
sharded over the axis; the gate (softmax top-k) is computed everywhere;
each device evaluates ITS experts for all tokens and the gate-weighted
combine is a single psum over ICI.  This dense-dispatch formulation is
EXACT (no capacity-factor token dropping) and keeps the collective
pattern trivial; a capacity-based all_to_all dispatch path is the
documented follow-up for sparse regimes.

What ``models/decoder.py``'s routed layer does that this one does not:
it is TOLD which experts it holds and computes only their part, sorts
the step's token-expert assignments by expert and runs grouped products
(``lax.ragged_dot``) over the kept ones instead of every expert on every
token, scores with a sigmoid and a correction bias and renormalises over
the chosen, and carries a shared expert.  It has no exchange: one
program, one share.  The two layers share the top-k selection
(:func:`top_k_route`, here): there is no second one in the tree.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.parallel.mesh import shard_map

__all__ = ["moe_apply", "moe_reference", "init_moe_params",
           "shard_moe_params", "top_k_route"]


def top_k_route(scores, k):
    """The ``k`` largest of ``scores`` along the last axis: (values,
    indices).  The routers' top-k: the gate here and
    ``models/decoder.py``'s routed layer (sigmoid or softmax) call it.
    The indexer's selection of keys is another, exact threshold a row
    (``ops/sparse_attention.py``), which no router shares."""
    return lax.top_k(scores, k)


def init_moe_params(rng, n_experts, features, hidden, out_features):
    """Gate + per-expert 2-layer MLP."""
    import numpy
    def u(shape, fan_in):
        return (rng.uniform(-1, 1, shape) /
                numpy.sqrt(fan_in)).astype(numpy.float32)
    return {
        "gate": u((features, n_experts), features),
        "w1": u((n_experts, features, hidden), features),
        "b1": numpy.zeros((n_experts, hidden), numpy.float32),
        "w2": u((n_experts, hidden, out_features), hidden),
        "b2": numpy.zeros((n_experts, out_features), numpy.float32),
    }


def _expert_mlp(w1, b1, w2, b2, x):
    h = jnp.tanh(jnp.dot(x, w1, preferred_element_type=jnp.float32) + b1)
    return jnp.dot(h, w2, preferred_element_type=jnp.float32) + b2


def _gate_weights(params, x, top_k):
    logits = jnp.dot(x, params["gate"],
                     preferred_element_type=jnp.float32)
    n_experts = logits.shape[-1]
    if top_k >= n_experts:
        return jax.nn.softmax(logits, axis=-1)
    top_vals, _ = top_k_route(logits, top_k)
    threshold = top_vals[..., -1:]
    masked = jnp.where(logits >= threshold, logits, -jnp.inf)
    return jax.nn.softmax(masked, axis=-1)


def moe_reference(params, x, top_k=2):
    """Single-device oracle."""
    gates = _gate_weights(params, x, top_k)  # (B, E)
    outs = jax.vmap(
        lambda w1, b1, w2, b2: _expert_mlp(w1, b1, w2, b2, x)
    )(params["w1"], params["b1"], params["w2"], params["b2"])  # (E,B,F)
    return jnp.einsum("be,ebf->bf", gates, outs).astype(x.dtype)


def shard_moe_params(mesh, params, axis="expert"):
    """Expert-dim leaves shard over the axis; the gate replicates."""
    out = {}
    for key, leaf in params.items():
        spec = P() if key == "gate" else P(axis)
        out[key] = jax.device_put(leaf, NamedSharding(mesh, spec))
    return out


def moe_apply(params, x, mesh, top_k=2, axis="expert",
              data_axis=None):
    """Expert-parallel forward: (B, F) -> (B, out).

    ``data_axis``: optionally shard tokens over a second mesh axis
    (dp x ep) — the gate-weighted combine still psums over the expert
    axis only; no cross-row traffic."""
    n_shards = mesh.shape[axis]

    def sharded(params_local, x_full):
        shard = lax.axis_index(axis)
        n_local = params_local["w1"].shape[0]
        gates = _gate_weights(
            {"gate": params_local["gate"]}, x_full,
            top_k)  # (B, E_total)
        local_out = jax.vmap(
            lambda w1, b1, w2, b2: _expert_mlp(w1, b1, w2, b2, x_full)
        )(params_local["w1"], params_local["b1"], params_local["w2"],
          params_local["b2"])  # (E_local, B, F_out)
        offset = shard * n_local
        local_gates = lax.dynamic_slice_in_dim(
            gates, offset, n_local, axis=1)  # (B, E_local)
        partial = jnp.einsum("be,ebf->bf", local_gates, local_out)
        return lax.psum(partial, axis).astype(x_full.dtype)

    fn = shard_map(
        sharded, mesh=mesh,
        in_specs=({"gate": P(), "w1": P(axis), "b1": P(axis),
                   "w2": P(axis), "b2": P(axis)}, P(data_axis)),
        out_specs=P(data_axis), check_vma=False)
    return fn(params, x)
