"""Sharding placement: batch over ``dp``, Megatron-pattern weights over ``tp``.

Design (scaling-book recipe): pick a mesh, annotate input shardings, let
XLA's SPMD partitioner insert the collectives. The UNet/CLIP modules stay
sharding-agnostic; placement happens on the param pytree and the batch
inputs, so the same compiled code serves 1 chip or a v5e-16 slice.

TP rules (applied by param-path pattern, the Megatron split):
- fused QKV / q / kv / fc1 / geglu proj / time+add MLP fc1: split the
  *output* features over ``tp`` (column parallel);
- out_proj / fc2 / ff_out / MLP fc2: split the *input* features over ``tp``
  (row parallel; XLA inserts the psum);
- convs: split output channels (last dim of HWIO) over ``tp``;
- norms, biases of row-parallel layers, embeddings: replicated.

The resident language model (models/lm.py) adds two axes a mesh may carry:
``ep`` splits an expert layer's stacked kernels by expert (every chip routes
over all experts and computes its own, ops/moe.py), ``vp`` splits the token
table's rows and the head's columns by vocabulary id. A mesh without these
axes leaves the leaves whole. A linear-attention mixer (module ``delta``:
its fused projections, convolution taps, decay rates, gated norm) and the
shared expert's gate are whole on every chip, as attention and the router
are in the deployment the shares stand for: the recurrence runs per head
over a state that is not split. Latent attention's low-rank projections
(one latent a position has one head: splitting by heads would copy the
cache to every chip), the residual streams' mixers (``attn_hc``,
``mlp_hc``) and the router's selection bias are whole on every chip too.
A short-convolution mixer (module ``short_conv``: the fused in-projection
whose columns are its two gates and its input, the taps, ``out_proj``) is
whole as well: a split of the fused columns over ``tp`` would not fall on
the three parts' borders; so is a state-space mixer (module ``ssm``: the
fused ``in_proj`` whose five column ranges are its gate, input, ``B``,
``C`` and ``dt``, the taps with their bias, ``A_log``, ``D``, ``dt_bias``,
the read-out's grouped norm, ``out_proj``). A looped model's exit gate
(``early_exit_gate``: one column and a bias) and the norms after its
sublayers (``input_norm_2``, ``post_attention_norm_2``) are whole on every
chip, as every norm is.
One chip's share (``LMConfig.experts_held``,
``vocab_held``) is what one position of those axes holds; the exchange that
adds the parts exists only on a mesh that has the axis.
"""

from __future__ import annotations


import jax


_COLUMN_ENDINGS = ("qkv", "q", "kv", "fc1", "proj", "time_fc1", "add_fc1",
                   "time_proj", "proj_in")
_ROW_ENDINGS = ("out_proj", "fc2", "ff_out", "time_fc2", "add_fc2",
                "proj_out")


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
#: modules of the language model whose leaves every chip holds whole
_LM_REPLICATED = ("shared_expert_gate", "q_a_proj", "q_b_proj",
                  "kv_a_proj_with_mqa", "kv_b_proj", "attn_hc", "mlp_hc",
                  "early_exit_gate")


def tp_spec_for(path: str, ndim: int):
    """PartitionSpec for one param, from its tree path (joined with '/')."""
    from jax.sharding import PartitionSpec as P

    parts = path.strip("/").split("/")
    leaf = parts[-1]              # kernel | bias | scale | embedding
    module = parts[-2] if len(parts) > 1 else ""

    if leaf in _EXPERT_LEAVES and module == "experts":
        return P("ep", None, None)
    if "delta" in parts[:-1] or "short_conv" in parts[:-1] \
            or "ssm" in parts[:-1] or module in _LM_REPLICATED \
            or leaf == "e_score_correction_bias":
        return P()
    if leaf == "embedding" and module == "embed_tokens":
        return P("vp", None)
    if leaf == "kernel" and module == "lm_head":
        return P(None, "vp")
    if leaf == "kernel":
        if module in _ROW_ENDINGS:
            # row-parallel: contract dim sharded
            return P(*([None] * (ndim - 2) + ["tp", None]))
        if module in _COLUMN_ENDINGS or module == "conv":
            return P(*([None] * (ndim - 1) + ["tp"]))
        if ndim >= 2:
            # default: treat as column-parallel (safe — no correctness risk,
            # XLA all-gathers where needed)
            return P(*([None] * (ndim - 1) + ["tp"]))
    if leaf == "bias" and module in _COLUMN_ENDINGS:
        return P("tp")
    # norms, embeddings, row-parallel biases: replicated
    return P()


def shard_params(params, mesh, use_tp: bool = True):
    """Place a param pytree on ``mesh``: TP rules if the mesh has tp>1,
    otherwise fully replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        return params
    model_axes = {a: mesh.shape.get(a, 1) for a in ("tp", "ep", "vp")}
    flat = jax.tree_util.tree_flatten_with_path(params)
    leaves, treedef = flat
    placed = []
    for keypath, leaf in leaves:
        if max(model_axes.values()) > 1 and use_tp \
                and hasattr(leaf, "ndim"):
            path = jax.tree_util.keystr(keypath, simple=True,
                                          separator="/")
            # an axis the mesh lacks (or has at size 1) splits nothing
            axes = [a if model_axes.get(a, 1) > 1 else None
                    for a in tp_spec_for(path, leaf.ndim)]
            spec = P(*axes) if any(axes) else P()
            # only shard dims that divide evenly; else replicate
            ok = True
            for dim, axis in enumerate(spec):
                if axis and leaf.shape[dim] % model_axes[axis] != 0:
                    ok = False
            sharding = NamedSharding(mesh, spec if ok else P())
        else:
            sharding = NamedSharding(mesh, P())
        placed.append(jax.device_put(leaf, sharding))
    return jax.tree_util.tree_unflatten(treedef, placed)


def place_batch(x, mesh):
    """Put a batch-major array on the mesh, axis 0 split over ``dp``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        return x
    spec = P(*(["dp"] + [None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def replicate(x, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        return x
    return jax.device_put(x, NamedSharding(mesh, P()))
