"""Mixture-of-experts MLP with grouped, capacity-based top-k routing.

TPU-first formulation (GShard/Switch style): instead of gathering each
expert's tokens with dynamic shapes — which XLA cannot tile onto the MXU —
tokens are routed through *static* dispatch/combine einsums against a
fixed per-expert capacity. Routing happens within fixed-size token groups
so the dispatch tensors stay [G, S, E, C] with constant S and C — memory
and FLOPs scale linearly in sequence length, not quadratically.

The expert axis of the weights carries the logical ``expert`` name, which
the mesh rules map to the ``ep`` axis
(``langstream_tpu.parallel.mesh.DEFAULT_RULES``); XLA then inserts the
all-to-alls between token-sharded activations and expert-sharded weights
automatically.

Two regimes:

- **training** (``capacity_factor`` set): tokens overflowing an expert's
  capacity are dropped (zero MLP delta) — the standard Switch trade that
  keeps compute balanced; the aux loss pushes the router toward balance.
- **exact / serving** (``capacity_factor=None``): every expert runs
  densely on every token and outputs combine with the renormalized top-k
  gates (zero weight for unselected experts). This matches a
  dropless-trained checkpoint (e.g. Mixtral) bit-for-bit in routing
  semantics, and is *strictly cheaper* than capacity-based dropless
  routing: dense costs E rows/token vs the dropless capacity bound's
  E·k rows/token, with no dispatch/combine einsums at all.

A ``valid`` mask keeps padding tokens from consuming capacity or skewing
the aux loss.

Reference parity: the reference has no local models at all (it proxies to
OpenAI et al. — see SURVEY.md §2.4, langstream-agents/langstream-ai-agents/
src/main/java/com/datastax/oss/streaming/ai/services/ServiceProvider.java:24).
MoE model support is net-new capability for the jax-local provider
(Mixtral-family), mirroring what the external providers offer.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def moe_capacity(
    group_tokens: int,
    num_experts: int,
    num_selected: int,
    capacity_factor: Optional[float],
) -> int:
    """Per-expert capacity within one routing group:
    ``ceil(factor * S * k / E)`` clamped to the all-fits bound ``S * k``
    (``None`` factor → that bound; note the exact regime in
    :func:`moe_mlp` uses the dense path instead, which is cheaper).
    """
    dropless = group_tokens * num_selected
    if capacity_factor is None:
        return dropless
    return max(
        1,
        min(
            dropless,
            int(
                math.ceil(
                    capacity_factor * group_tokens * num_selected / num_experts
                )
            ),
        ),
    )


def moe_routing(
    logits: jnp.ndarray,  # [S, E] float32 router logits for one group
    num_selected: int,
    capacity: int,
    valid: Optional[jnp.ndarray] = None,  # [S] bool; False = padding
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing with per-expert capacity inside one group.

    Returns:
      dispatch  [S, E, C] float  — 0/1 routing of tokens into expert rows
      combine   [S, E, C] float  — dispatch weighted by normalized gates
      aux_loss  scalar           — Switch-style load-balancing loss
                                   (over valid tokens only)
    """
    num_tokens, num_experts = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)  # [S, E]
    gate_vals, gate_idx = jax.lax.top_k(probs, num_selected)  # [S, k]
    # renormalize the selected gates so the expert mix sums to 1 per token
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    onehot = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.float32)  # [S,k,E]
    if valid is not None:
        onehot = onehot * valid[:, None, None].astype(jnp.float32)
    # Position of each (token, choice) within its expert: priority is
    # choice-major (all first choices before any second choice), so a
    # token's primary expert wins capacity over others' secondaries.
    flat = onehot.transpose(1, 0, 2).reshape(num_selected * num_tokens, num_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat  # [k*S, E]
    pos = pos_flat.reshape(num_selected, num_tokens, num_experts).transpose(1, 0, 2)
    pos_in_expert = (pos * onehot).sum(-1).astype(jnp.int32)  # [S, k]
    # masked-out choices (padding tokens) have all-zero onehot rows
    fits = (pos_in_expert < capacity) & (onehot.sum(-1) > 0)  # [S, k]

    pos_onehot = jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)
    pos_onehot = pos_onehot * fits[..., None].astype(jnp.float32)
    dispatch = jnp.einsum("ske,skc->sec", onehot, pos_onehot)
    combine = jnp.einsum("sk,ske,skc->sec", gate_vals, onehot, pos_onehot)

    # load-balance loss: E * sum_e mean(frac routed to e) * mean(prob e),
    # means taken over valid tokens only
    if valid is None:
        denom = jnp.float32(num_tokens)
        probs_masked = probs
    else:
        denom = jnp.maximum(valid.sum().astype(jnp.float32), 1.0)
        probs_masked = probs * valid[:, None].astype(jnp.float32)
    # fraction over ALL top-k selections, normalized by k (Switch/Mixtral
    # formulation): second-choice load gets balancing pressure too
    frac_routed = onehot.sum(axis=(0, 1)) / (num_selected * denom)
    mean_prob = probs_masked.sum(axis=0) / denom
    aux_loss = num_experts * jnp.sum(frac_routed * mean_prob)
    return dispatch, combine, aux_loss


def moe_mlp(
    x: jnp.ndarray,        # [..., H]
    router_w: jnp.ndarray,  # [H, E]
    w_gate: jnp.ndarray,   # [E, H, F]
    w_up: jnp.ndarray,     # [E, H, F]
    w_down: jnp.ndarray,   # [E, F, H]
    *,
    num_selected: int = 2,
    capacity_factor: Optional[float] = 2.0,
    group_size: int = 64,
    valid: Optional[jnp.ndarray] = None,  # [...] bool, x's leading shape
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SwiGLU expert MLP over grouped capacity-routed tokens.

    Returns (output with x's shape, load-balancing aux loss). All shapes
    static: dispatch/combine are [G, S, E, C] einsum operands, so under an
    ``ep``-sharded mesh the per-expert matmuls stay dense MXU work and the
    routing einsums become all-to-alls. ``capacity_factor=None`` = the
    dropless serving regime.
    """
    orig_shape = x.shape
    hidden = x.shape[-1]
    x2 = x.reshape(-1, hidden)
    num_tokens = x2.shape[0]
    num_experts = router_w.shape[-1]
    num_selected = min(num_selected, num_experts)

    if capacity_factor is None:
        valid2 = None if valid is None else valid.reshape(-1)
        y, aux = _moe_mlp_dense(
            x2, router_w, w_gate, w_up, w_down,
            num_selected=num_selected, valid=valid2,
        )
        return y.reshape(orig_shape), aux

    group = min(group_size, num_tokens)
    pad = (-num_tokens) % group
    valid2 = (
        jnp.ones((num_tokens,), dtype=bool)
        if valid is None
        else valid.reshape(-1)
    )
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        valid2 = jnp.pad(valid2, (0, pad))
    num_groups = x2.shape[0] // group
    xg = x2.reshape(num_groups, group, hidden)
    vg = valid2.reshape(num_groups, group)
    capacity = moe_capacity(group, num_experts, num_selected, capacity_factor)

    logits = jnp.einsum(
        "gsh,he->gse", xg.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    dispatch, combine, aux = jax.vmap(
        lambda l, v: moe_routing(l, num_selected, capacity, v)
    )(logits, vg)
    aux_loss = aux.mean()

    dtype = x2.dtype
    expert_in = jnp.einsum("gsec,gsh->egch", dispatch.astype(dtype), xg)
    gate = jnp.einsum("egch,ehf->egcf", expert_in, w_gate)
    up = jnp.einsum("egch,ehf->egcf", expert_in, w_up)
    expert_out = jnp.einsum("egcf,efh->egch", jax.nn.silu(gate) * up, w_down)
    y = jnp.einsum("gsec,egch->gsh", combine.astype(dtype), expert_out)
    y = y.reshape(-1, hidden)[:num_tokens]
    return y.reshape(orig_shape), aux_loss


def _moe_mlp_dense(
    x2: jnp.ndarray,        # [T, H]
    router_w: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    num_selected: int,
    valid: Optional[jnp.ndarray],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact MoE: every expert runs on every token; outputs combine with
    renormalized top-k gate weights (zero for unselected experts). No
    token is ever dropped and no dispatch tensors exist. Under an
    ep-sharded mesh the [E, T, F] activations shard over ep, and XLA
    reduces the final combine over the expert axis with one psum."""
    num_experts = router_w.shape[-1]
    logits = jnp.einsum(
        "th,he->te", x2.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, num_selected)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )
    onehot = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.float32)
    gates = jnp.einsum("tk,tke->te", gate_vals, onehot)  # [T, E]

    dtype = x2.dtype
    gate_proj = jnp.einsum("th,ehf->etf", x2, w_gate)
    up_proj = jnp.einsum("th,ehf->etf", x2, w_up)
    out = jnp.einsum("etf,efh->eth", jax.nn.silu(gate_proj) * up_proj, w_down)
    y = jnp.einsum("te,eth->th", gates.astype(dtype), out)

    if valid is None:
        denom = jnp.float32(x2.shape[0])
        probs_masked = probs
        first_choice = onehot[:, 0]
    else:
        vf = valid.astype(jnp.float32)
        denom = jnp.maximum(vf.sum(), 1.0)
        probs_masked = probs * vf[:, None]
        first_choice = onehot[:, 0] * vf[:, None]
    aux_loss = num_experts * jnp.sum(
        (first_choice.sum(0) / denom) * (probs_masked.sum(0) / denom)
    )
    return y, aux_loss


# --------------------------------------------------------------------- #
# serving over a chip's share of the experts: computed only where routed
# --------------------------------------------------------------------- #
def group_limited_routing(
    logits: jnp.ndarray,  # [T, E] float32 router logits over ALL experts
    *,
    groups: int,
    groups_kept: int,
    num_selected: int,
    scaling_factor: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Group-limited greedy routing: softmax over all ``E`` experts, a
    group's score the largest probability among its ``E / groups``
    experts, the ``groups_kept`` best groups stay and the others'
    probabilities count as 0, the ``num_selected`` largest remaining are
    the token's experts with weights ``scaling_factor * p_e``, NOT
    renormalised. Returns (weights [T, k] float32, experts [T, k])."""
    tokens, num_experts = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    by_group = probs.reshape(tokens, groups, num_experts // groups)
    _, best = jax.lax.top_k(by_group.max(axis=-1), groups_kept)
    keep = jnp.zeros((tokens, groups), dtype=bool).at[
        jnp.arange(tokens)[:, None], best
    ].set(True)
    masked = jnp.where(keep[:, :, None], by_group, 0.0).reshape(
        tokens, num_experts
    )
    weights, chosen = jax.lax.top_k(masked, num_selected)
    return weights * scaling_factor, chosen


def sigmoid_bias_routing(
    logits: jnp.ndarray,  # [T, E] float32 router logits over ALL experts
    bias: jnp.ndarray,    # [E] float32: moves the selection, never a weight
    *,
    num_selected: int,
    scaling_factor: float,
    renormalise: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sigmoid routing with a selection bias: every expert's score is
    ``s_e = sigmoid(logit_e)`` on its own (no softmax over the experts),
    the token's experts are the ``num_selected`` largest of ``s + bias``,
    and their weights are the UNBIASED ``s_e``, divided (``renormalise``)
    by ``sum of the chosen s_e + 1e-6``, times ``scaling_factor``. The
    bias balances the experts' load without touching the mixture.
    Returns (weights [T, k] float32, experts [T, k])."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), num_selected)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalise:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return weights * scaling_factor, chosen


def _grouped_kernel(group_ref, active_ref, layer_ref, x_ref, w_ref, out_ref):
    """One row tile of one expert against one column tile of its weight
    (the whole contraction at once); tiles past the last active one are
    skipped (their blocks are not moved either: the index maps clamp)."""
    del group_ref, layer_ref

    @pl.when(pl.program_id(1) < active_ref[0])
    def _compute():
        out_ref[...] = jnp.dot(
            x_ref[...], w_ref[0], preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)


def _column_tile(k: int, n: int) -> int:
    """Widest multiple of 128 dividing ``n`` whose [k, tile] bf16 weight
    block stays near 5 MB (two are in flight)."""
    best = n if n % 128 else 128
    for tile in range(128, n + 1, 128):
        if n % tile == 0 and k * tile * 2 <= 5 * 2 ** 20 + 2 ** 18:
            best = tile
    return best


def grouped_matmul(
    x: jnp.ndarray,           # [M, K] rows sorted by expert, every
                              # expert's rows starting on a tile boundary
    w: jnp.ndarray,           # [L, G, K, N]: every layer's stack
    layer: jnp.ndarray,       # [] int32: which layer's experts
    tile_group: jnp.ndarray,  # [M / tile] int32: the expert of each tile
    num_active: jnp.ndarray,  # [] int32: tiles that hold routed rows
    group_sizes: jnp.ndarray,  # [G] int32, multiples of ``tile``
    *,
    tile: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[r] = x[r] @ w[layer, expert of r's tile]`` for the first
    ``num_active`` tiles of ``tile`` rows; rows past them are not
    computed and hold nothing meaningful. On TPU a Pallas kernel
    (``moe_grouped_matmul``): the computed rows are exactly ``num_active
    * tile``, and the weights are read where they lie in the layers'
    STACK (the layer is one more prefetched scalar of the index maps, as
    in ``ops/decode_kernel.py``: a slab sliced out of the stack for a
    custom call is a copy of it, 629 MB a leaf a layer at the
    DeepSeek-V2 sizes). Elsewhere ``jax.lax.ragged_dot`` over the same
    layout."""
    from langstream_tpu.ops.flash_attention import on_tpu

    rows, k = x.shape
    layers, groups, _, n = w.shape
    if not (on_tpu() or interpret):
        return jax.lax.ragged_dot(x, w[layer], group_sizes)
    tiles = rows // tile
    tn = _column_tile(k, n)

    def clamp(t, active):
        return jnp.minimum(t, jnp.maximum(active[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, tiles),
        in_specs=[
            pl.BlockSpec(
                (tile, k), lambda j, t, group, active, lyr: (clamp(t, active), 0)
            ),
            pl.BlockSpec(
                (1, k, tn),
                lambda j, t, group, active, lyr: (
                    lyr[0] * groups + group[clamp(t, active)], 0, j
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile, tn), lambda j, t, group, active, lyr: (clamp(t, active), j)
        ),
    )
    return pl.pallas_call(
        _grouped_kernel,
        name="moe_grouped_matmul",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 2 ** 20,
        ),
        interpret=interpret,
    )(
        tile_group.astype(jnp.int32),
        jnp.reshape(num_active, (1,)).astype(jnp.int32),
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        x,
        # layer and expert merge into one leading axis (a bitcast)
        w.reshape(layers * groups, k, n),
    )


def routed_tile(tokens: int, num_selected: int, num_experts: int) -> int:
    """Row tile of the grouped path, from shapes: the power of two at or
    above the rows an expert expects (``tokens * k / E``), between 16 (a
    bf16 tile's sublanes) and 128 (the MXU's rows). A decode step of 64
    tokens takes 16, a 4,096-token prefill 128."""
    expected = max(1, -(-tokens * num_selected // num_experts))
    return int(min(128, max(16, 1 << (expected - 1).bit_length())))


def moe_mlp_held(
    x2: jnp.ndarray,        # [T, H]
    router_w: jnp.ndarray,  # [H, E]: the router over ALL experts
    w_gate: jnp.ndarray,    # [L, held, H, F]: every layer's experts
    w_up: jnp.ndarray,      # [held_first, held_first + held)
    w_down: jnp.ndarray,    # [L, held, F, H]
    *,
    layer=0,                # [] int32: which layer of the stacks
    held_first: int,
    route,                  # the routing rule: logits [T, E] float32 ->
                            # (weights [T, k] float32, experts [T, k])
    valid: Optional[jnp.ndarray] = None,  # [T] bool; False = padding
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A chip's share of a routed mixture: the router scores all ``E``
    experts by the rule ``route`` (:func:`group_limited_routing` or
    :func:`sigmoid_bias_routing`, its sizes bound), this chip computes ``sum_e w_e SwiGLU_e(x)`` over the experts
    it holds, for the tokens routed to them and for no others. No token is
    dropped and nothing stands in for the absent experts.

    Static shapes: the (token, expert) assignments that meet a held
    expert are sorted by expert, each expert's rows start on a tile
    boundary, and the grouped matmul computes the tiles that hold rows.
    The layout has room for the worst case (every assignment held:
    ``T * k + held * tile`` rows); what is computed is ``ceil(rows_e /
    tile) * tile`` summed over the held experts.

    Returns (y [T, H], counters int32 [3 + held]: assignments routed (valid
    tokens x k), assignments that met a held expert, rows the expert
    matmuls computed (padding included), tokens by held expert)."""
    tokens, hidden = x2.shape
    held = w_gate.shape[1]
    num_experts = router_w.shape[-1]
    logits = jnp.einsum(
        "th,he->te", x2.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    weights, chosen = route(logits)
    num_selected = chosen.shape[-1]
    local = chosen - held_first                       # [T, k]
    met = (local >= 0) & (local < held)
    if valid is not None:
        met = met & valid[:, None]
    tile = routed_tile(tokens, num_selected, num_experts)
    pairs = tokens * num_selected
    rows = -(-pairs // tile) * tile + held * tile
    # sort the assignments by expert; those that meet no held expert go
    # last (group ``held``) and get no row
    flat_group = jnp.where(met, local, held).reshape(pairs)
    order = jnp.argsort(flat_group, stable=True)
    sorted_group = flat_group[order]
    counts = jnp.zeros((held + 1,), jnp.int32).at[flat_group].add(1)[:held]
    padded = -(-counts // tile) * tile                # rows an expert takes
    starts = jnp.cumsum(counts) - counts              # in the sorted order
    padded_starts = jnp.cumsum(padded) - padded       # in the layout
    safe_group = jnp.minimum(sorted_group, held - 1)
    rank = jnp.arange(pairs) - starts[safe_group]
    dest_sorted = jnp.where(
        sorted_group < held, padded_starts[safe_group] + rank, rows - 1
    )
    # the row of every (token, choice); ``rows - 1`` is never an active
    # row's (the layout has a tile to spare past the worst case)
    dest = jnp.zeros((pairs,), jnp.int32).at[order].set(dest_sorted)
    row_token = jnp.zeros((rows,), jnp.int32).at[dest_sorted].set(
        (order // num_selected).astype(jnp.int32)
    )
    num_active = padded.sum() // tile
    tile_group = jnp.minimum(
        jnp.searchsorted(
            jnp.cumsum(padded), jnp.arange(rows // tile) * tile, side="right"
        ),
        held - 1,
    ).astype(jnp.int32)

    def matmul(lhs, w):
        return grouped_matmul(
            lhs, w, layer, tile_group, num_active, padded, tile=tile,
            interpret=interpret,
        )

    x_rows = x2[row_token]                            # [rows, H]
    hidden_rows = jax.nn.silu(matmul(x_rows, w_gate)) * matmul(x_rows, w_up)
    out_rows = matmul(hidden_rows, w_down)            # [rows, H]
    picked = out_rows[dest].reshape(tokens, num_selected, hidden)
    gate = jnp.where(met, weights, 0.0)
    # rows past the active tiles hold nothing meaningful: masked, not
    # multiplied by a zero weight (0 * nan is nan)
    picked = jnp.where(met[:, :, None], picked, 0)
    y = jnp.einsum(
        "tk,tkh->th", gate.astype(jnp.float32), picked.astype(jnp.float32)
    ).astype(x2.dtype)
    routed = (
        jnp.int32(pairs) if valid is None
        else valid.sum().astype(jnp.int32) * num_selected
    )
    counters = jnp.concatenate([
        jnp.stack([routed, met.sum().astype(jnp.int32), num_active * tile]),
        counts,
    ]).astype(jnp.int32)
    return y, counters
