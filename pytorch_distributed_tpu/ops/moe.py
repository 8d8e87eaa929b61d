"""Mixture-of-Experts MLP with expert parallelism (the ``ep`` mesh axis).

Not present in the reference (SURVEY.md §2 — DDP/ZeRO-1/FSDP recipes
only); built TPU-first as a capability extension. ONE layer,
:class:`MoEMLP`, parametrised by how it scores and selects:

* ``scoring="softmax"`` with ``k`` renormalised gates is the Switch /
  Mixtral router; ``scoring="sigmoid"`` with a selection bias, a group
  limit (``n_group`` / ``topk_group``), gates normalised over all ``k``
  selected and scaled by ``routed_scale``, and a shared expert
  (``shared_d_ff``) is the DeepSeek-V3 one. The router and its scores
  are float32 either way.
* ``held=(first, count)`` tells the layer which contiguous range of the
  ``num_experts`` it routes over it HOLDS: its expert tensors carry
  ``count`` experts, it computes those experts' part of every token's
  sum (the gates still normalise over all ``k`` selected, held here or
  not) and adds the shared expert once. On one chip nothing is
  exchanged, and nothing stands in for the absent chips.

Two dispatches share one parameter tree. ``capacity_factor=None`` is
DROP-FREE (serving, HF parity): the token-expert pairs routed to held
experts are sorted by expert into row tiles and go through one grouped
matrix product per expert matrix (:func:`expert_gmm`, a Pallas kernel:
each row tile multiplies its own expert's weights, picked through a
scalar-prefetched table, so an expert's weights stream once per tile
and an expert nobody chose streams nothing). A finite factor is the
Switch bounded-capacity dispatch (dense one-hot dispatch/combine
einsums, static shapes, overflow dropped to the residual path) — the
training-throughput mode; XLA lowers its token movement to all-to-alls
over ICI when the expert dim is sharded ``P("ep")``.

The Switch load-balance auxiliary loss is exposed via ``sow`` under
``("intermediates", "moe_aux_loss")`` — add it to the task loss scaled by
``aux_loss_weight``. The drop-free dispatch also sows ``route_stats``
(int32 ``[3]``: pairs routed to held experts, held experts that got at
least one, the most any one got), which the serving engine sends down
with the tokens it already fetches.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.ops.flash_attention import _mxu_dot
from pytorch_distributed_tpu.runtime.precision import current_policy

# --------------------------------------------------------------------------
# the grouped matrix product
# --------------------------------------------------------------------------

def _interpret() -> bool:
    return jax.default_backend() != "tpu"


_W_BLOCK_BYTES = 4 * 1024 * 1024  # one weight block in VMEM (two in flight)


def _col_block(K: int, N: int, itemsize: int) -> int:
    """Widest column block of a ``[K, N]`` expert matrix that keeps one
    ``[K, block]`` slab under ``_W_BLOCK_BYTES``: a divisor of ``N``, a
    multiple of 128 lanes where ``N`` has one."""
    if K * N * itemsize <= _W_BLOCK_BYTES or N % 128:
        return N
    best = 128
    for b in range(128, N + 1, 128):
        if N % b == 0 and K * b * itemsize <= _W_BLOCK_BYTES:
            best = b
    return best


def _gmm_body(te_ref, nt_ref, x_ref, w_ref, o_ref):
    del te_ref  # read by the index maps only

    @pl.when(pl.program_id(0) < nt_ref[0])
    def _live():
        o_ref[...] = _mxu_dot(x_ref[...], w_ref[...], 1, 0).astype(
            o_ref.dtype
        )

    @pl.when(pl.program_id(0) >= nt_ref[0])
    def _dead():  # a tile past the last routed pair: nothing read of it
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_call(x, w, tile_expert, n_tiles, tm):
    M, K = x.shape
    E, _, N = w.shape
    tn = _col_block(K, N, w.dtype.itemsize)
    nj = N // tn

    def w_map(i, j, te, nt):
        # a dead tile names the block the last live step left in VMEM,
        # so the pipeline fetches no weights for it
        return te[i], 0, jnp.where(i < nt[0], j, nj - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // tm, nj),
        in_specs=[
            pl.BlockSpec((tm, K), lambda i, j, te, nt: (i, 0)),
            pl.BlockSpec((None, K, tn), w_map),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, te, nt: (i, j)),
    )
    return pl.pallas_call(
        _gmm_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024,
        ),
        interpret=_interpret(),
        name="expert_gmm",
    )(tile_expert.astype(jnp.int32), n_tiles.astype(jnp.int32).reshape(1),
      x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def expert_gmm(x, w, tile_expert, n_tiles, tm):
    """Grouped matrix product: rows ``[i * tm, (i + 1) * tm)`` of
    ``x [M, K]`` times ``w[tile_expert[i]] [K, N]``, for the first
    ``n_tiles`` row tiles; the rest come back zero. ``M`` is a multiple
    of ``tm``; tiles of one expert are consecutive, and ``tile_expert``
    of a dead tile repeats the last live one (:func:`sorted_dispatch`
    builds both)."""
    return _gmm_call(x, w, tile_expert, n_tiles, tm)


def _gmm_fwd(x, w, tile_expert, n_tiles, tm):
    return _gmm_call(x, w, tile_expert, n_tiles, tm), (
        x, w, tile_expert, n_tiles,
    )


def _gmm_bwd(tm, res, dy):
    x, w, tile_expert, n_tiles = res
    dx = _gmm_call(dy, jnp.swapaxes(w, 1, 2), tile_expert, n_tiles, tm)
    live = (jnp.arange(x.shape[0] // tm) < n_tiles)[:, None, None]
    per_tile = jnp.einsum(
        "tmk,tmn->tkn", x.reshape(-1, tm, x.shape[1]),
        dy.reshape(-1, tm, dy.shape[1]),
        preferred_element_type=jnp.float32,
    )
    dw = jax.ops.segment_sum(
        jnp.where(live, per_tile, 0.0), tile_expert,
        num_segments=w.shape[0],
    ).astype(w.dtype)
    return dx, dw, None, None


expert_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def row_tile(num_pairs: int) -> int:
    """Rows of one tile of the grouped product for ``num_pairs``
    token-expert pairs at most: small tiles while an expert sees a few
    rows (a decode tick), the MXU's 128 once it sees many."""
    if num_pairs >= 2048:
        return 128
    return 32 if num_pairs >= 256 else 16


def sorted_dispatch(local, num_held: int, tm: int):
    """Sort token-expert pairs by held expert into row tiles.

    ``local [N]`` is each pair's expert as an index into the held range,
    ``num_held`` for a pair routed elsewhere. Every held expert's pairs
    are laid out from a tile boundary on (``tm`` rows a tile), so a
    tile belongs to one expert. Returns ``(pair_of_row [M], row_of_pair
    [N], tile_expert [M // tm], n_tiles, sizes [num_held])`` with ``M =
    (ceil(N / tm) + num_held) * tm`` rows (the static worst case);
    ``pair_of_row`` is ``N`` for padding and ``row_of_pair`` is ``M``
    for a pair not held here."""
    N = local.shape[0]
    tiles = -(-N // tm) + num_held
    M = tiles * tm
    order = jnp.argsort(local, stable=True)
    sorted_e = local[order]
    sizes = jnp.zeros(num_held + 1, jnp.int32).at[local].add(1)[:num_held]
    tiles_per = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_per)
    n_tiles = tile_end[-1]
    first_row = (tile_end - tiles_per) * tm        # of each expert
    first_pair = jnp.cumsum(sizes) - sizes         # in sorted order
    e = jnp.minimum(sorted_e, num_held - 1)
    dest = first_row[e] + jnp.arange(N, dtype=jnp.int32) - first_pair[e]
    dest = jnp.where(sorted_e < num_held, dest, M)
    pair_of_row = jnp.full(M, N, jnp.int32).at[dest].set(
        order.astype(jnp.int32), mode="drop"
    )
    row_of_pair = jnp.full(N, M, jnp.int32).at[order].set(dest)
    t = jnp.minimum(jnp.arange(tiles), jnp.maximum(n_tiles - 1, 0))
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= t[:, None], axis=1), num_held - 1
    ).astype(jnp.int32)
    return pair_of_row, row_of_pair, tile_expert, n_tiles, sizes


def expert_params(module, experts: int, D: int, F: int, gated: bool,
                  lead: Tuple[int, ...] = ()):
    """``(w_in, w_gate or None, w_out)`` declared on ``module``:
    ``[*lead, experts, D, F]`` in and ``[*lead, experts, F, D]`` out."""
    policy = current_policy()
    init = nn.initializers.lecun_normal(batch_axis=tuple(range(len(lead))))
    make = lambda name, shape: module.param(  # noqa: E731
        name, init, lead + (experts,) + shape, policy.param_dtype)
    return (
        make("w_in", (D, F)),
        make("w_gate", (D, F)) if gated else None,
        make("w_out", (F, D)),
    )


def route(scores, k: int, *, bias=None, n_group: int = 1,
          topk_group: int = 1, scale: float = 1.0):
    """``(gates [T, k], experts [T, k])`` from ``scores [T, E]`` (f32).

    Selection runs on ``scores + bias`` (the bias moves who is chosen,
    never a gate's value): with ``n_group > 1`` a group's score is the
    sum of its two largest, the ``topk_group`` best groups are kept and
    the ``k`` largest among them win (``lax.top_k``: a tie goes to the
    lower index). Gates are the chosen experts' plain scores, normalised
    over all ``k`` and scaled."""
    T, E = scores.shape
    choice = scores if bias is None else scores + bias[None, :]
    if n_group > 1:
        per_group = choice.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, topk_group)      # [T, g]
        keep = jnp.any(
            kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1
        )                                                     # [T, G]
        choice = jnp.where(
            jnp.repeat(keep, E // n_group, axis=1), choice, 0.0
        )
    _, experts = jax.lax.top_k(choice, k)
    gates = jnp.take_along_axis(scores, experts, axis=1)
    gates = gates / jnp.clip(jnp.sum(gates, -1, keepdims=True), 1e-20)
    return gates * scale, experts


class MoEMLP(nn.Module):
    """Drop-in replacement for a transformer FFN block.

    ``activation="gelu"`` is the Switch-Transformer two-matrix expert;
    ``"swiglu"`` adds a per-expert gate matrix (``w2(silu(w1 x)*w3 x)``,
    the Mixtral expert — w_gate/w_in/w_out here map to HF's w1/w3/w2).

    ``capacity_factor=None`` disables token dropping — the serving /
    HF-parity mode: every selected pair is computed exactly, through the
    sorted grouped product (module docstring). Finite factors use the
    Switch bounded-capacity dispatch (overflow tokens dropped to the
    residual path) — the training-throughput mode. The param tree is
    identical either way, so one checkpoint serves both.

    ``scoring``, ``select_bias``, ``n_group`` / ``topk_group``,
    ``routed_scale`` and ``shared_d_ff`` parametrise the router and the
    shared expert (:func:`route`); ``held=(first, count)`` cuts the
    layer to the experts one chip of an expert-parallel group holds
    (drop-free dispatch only).
    """

    num_experts: int
    d_ff: int
    k: int = 2
    capacity_factor: Optional[float] = 1.25
    activation: str = "gelu"  # gelu | swiglu
    scoring: str = "softmax"  # softmax | sigmoid
    select_bias: bool = False
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    shared_d_ff: Optional[int] = None
    held: Optional[Tuple[int, int]] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, experts=None, layer=None):
        """``experts`` hands in the expert tensors instead of declaring
        them: ``{"w_in", "w_out"[, "w_gate"]}`` stacked ``[L, held, ..]``
        over a layer loop that gives ``layer``, this layer's index. The
        grouped product then reads plane ``layer`` of each stack in
        place; a kernel operand sliced out of a scanned leaf is copied
        every iteration (PERF.md, PR 27)."""
        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(
                f"activation must be 'gelu' or 'swiglu', got "
                f"{self.activation!r}"
            )
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring must be 'softmax' or 'sigmoid', got "
                f"{self.scoring!r}"
            )
        if self.held is not None and self.capacity_factor is not None:
            raise ValueError(
                "held=(first, count) cuts the drop-free dispatch only "
                "(capacity_factor=None)"
            )
        policy = current_policy()
        *batch_dims, D = x.shape
        E, F, K = self.num_experts, self.d_ff, self.k
        first, held = self.held or (0, E)
        tokens = x.reshape(-1, D)
        T = tokens.shape[0]
        ctype = policy.compute_dtype

        # ---- router (f32: tiny, and gate precision matters) -------------
        logits = nn.Dense(
            E, use_bias=False, dtype=jnp.float32,
            param_dtype=policy.param_dtype, name="router",
        )(tokens.astype(jnp.float32))  # [T, E]
        if self.scoring == "softmax":
            probs = scores = jax.nn.softmax(logits, axis=-1)
        else:
            scores = jax.nn.sigmoid(logits)
            probs = scores / jnp.sum(scores, -1, keepdims=True)
        bias = None
        if self.select_bias:
            bias = self.param(
                "router_bias", nn.initializers.zeros, (E,),
                policy.param_dtype,
            ).astype(jnp.float32)
        gate_vals, expert_idx = route(
            scores, K, bias=bias, n_group=self.n_group,
            topk_group=self.topk_group, scale=self.routed_scale,
        )  # [T, K]

        # ---- expert params: ONE tree for both dispatch modes, so a
        # model trained with a finite capacity_factor serves drop-free
        # from the same checkpoint ---------------------------------------
        first_tile_expert = 0
        if experts is None:
            w_in, w_gate, w_out = expert_params(
                self, held, D, F, self.activation == "swiglu"
            )
        elif self.capacity_factor is not None:
            raise ValueError("stacked experts serve the drop-free dispatch")
        else:
            w_in, w_out, w_gate = (
                experts["w_in"], experts["w_out"], experts.get("w_gate")
            )
            if w_in.dtype == ctype:
                # every layer's experts as one [L * held, ..] operand
                flat = lambda w: None if w is None else w.reshape(  # noqa: E731
                    (-1,) + w.shape[-2:])
                w_in, w_gate, w_out = flat(w_in), flat(w_gate), flat(w_out)
                first_tile_expert = layer * held
            else:  # cast what one layer reads, not the stack
                mine = lambda w: None if w is None else (  # noqa: E731
                    jax.lax.dynamic_index_in_dim(w, layer, keepdims=False))
                w_in, w_gate, w_out = mine(w_in), mine(w_gate), mine(w_out)

        def act(h, g):
            return nn.gelu(h) if g is None else nn.silu(g) * h

        if self.capacity_factor is None:
            # ---- drop-free: the pairs routed to held experts, sorted by
            # expert into row tiles, through one grouped product per
            # expert matrix; a pair held elsewhere costs nothing here
            local = (expert_idx - first).reshape(-1)
            local = jnp.where(
                (local >= 0) & (local < held), local, held
            ).astype(jnp.int32)
            tm = row_tile(T * K)
            pair_of_row, row_of_pair, tile_expert, n_tiles, sizes = (
                sorted_dispatch(local, held, tm)
            )
            padded = jnp.concatenate(
                [tokens.astype(ctype), jnp.zeros((1, D), ctype)]
            )
            rows = padded[jnp.where(pair_of_row < T * K, pair_of_row // K, T)]
            gmm = functools.partial(
                expert_gmm, tile_expert=tile_expert + first_tile_expert,
                n_tiles=n_tiles, tm=tm,
            )
            h = gmm(rows, w_in.astype(ctype))
            g = None if w_gate is None else gmm(rows, w_gate.astype(ctype))
            out_rows = gmm(act(h, g), w_out.astype(ctype))       # [M, D]
            out_rows = jnp.concatenate(
                [out_rows, jnp.zeros((1, D), out_rows.dtype)]
            )
            y = jnp.sum(
                out_rows[row_of_pair].reshape(T, K, D).astype(jnp.float32)
                * gate_vals[:, :, None], axis=1,
            ).astype(ctype)
            self.sow("intermediates", "route_stats", jnp.stack([
                jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes),
            ]).astype(jnp.int32))
        else:
            # ---- Switch-style bounded-capacity dispatch (training):
            # per-expert queue C, overflow dropped to the residual path
            # one-hot over experts per (token, k): [T, K, E]
            sel = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
            C = max(1, int(K * T * self.capacity_factor / E + 0.999))
            # position of each (t, k) within its expert's queue, k-major
            # so primary assignments win capacity over secondary ones
            flat_sel = sel.transpose(1, 0, 2).reshape(K * T, E)  # k-major
            pos_flat = jnp.cumsum(flat_sel, axis=0) - 1.0  # [K*T, E]
            pos = pos_flat.reshape(K, T, E).transpose(1, 0, 2)  # [T, K, E]
            in_cap = (pos < C).astype(jnp.float32)
            kept = sel * in_cap  # [T, K, E]
            slot = jax.nn.one_hot(
                jnp.sum(pos * sel, -1).astype(jnp.int32), C,
                dtype=jnp.float32,
            )  # [T, K, C]
            # dispatch: does token t occupy (expert e, slot c)? [T, E, C]
            dispatch = jnp.einsum("tke,tkc->tec", kept, slot)
            combine = jnp.einsum(
                "tke,tkc,tk->tec", kept, slot, gate_vals.astype(jnp.float32)
            )
            expert_in = jnp.einsum(
                "tec,td->ecd", dispatch.astype(ctype), tokens.astype(ctype)
            )
            h = jnp.einsum("ecd,edf->ecf", expert_in, w_in.astype(ctype))
            g = None if w_gate is None else jnp.einsum(
                "ecd,edf->ecf", expert_in, w_gate.astype(ctype)
            )
            expert_out = jnp.einsum(
                "ecf,efd->ecd", act(h, g), w_out.astype(ctype)
            )
            y = jnp.einsum(
                "tec,ecd->td", combine.astype(ctype), expert_out
            )

        if self.shared_d_ff is not None:
            # the shared expert: every token, added once
            dense = lambda feats, name: nn.Dense(  # noqa: E731
                feats, use_bias=False, dtype=ctype,
                param_dtype=policy.param_dtype, name=name,
            )
            t = tokens.astype(ctype)
            y = y + dense(D, "shared_down")(act(
                dense(self.shared_d_ff, "shared_up")(t),
                dense(self.shared_d_ff, "shared_gate")(t)
                if self.activation == "swiglu" else None,
            ))

        # ---- Switch load-balance aux loss ------------------------------
        # fraction of tokens routed to e (primary assignment) x mean router
        # prob for e, scaled by E — minimised when routing is uniform
        primary = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
        aux = E * jnp.sum(
            jnp.mean(primary, axis=0) * jnp.mean(probs, axis=0)
        )
        self.sow("intermediates", "moe_aux_loss", aux)

        return y.reshape(*batch_dims, D).astype(x.dtype)


def moe_partition_rules(ep_axis: str = "ep", tp_axis: str = "tp"):
    """Partition rules for MoE params: experts over ``ep``, the FFN hidden
    dim over ``tp`` (composes with Megatron-style TP inside each expert).
    Feed to the Strategy ``extra_rules`` machinery."""
    from jax.sharding import PartitionSpec as P

    return [
        ("router/kernel", P(None, None)),
        ("router_bias", P(None)),
        ("w_in", P(ep_axis, None, tp_axis)),
        ("w_gate", P(ep_axis, None, tp_axis)),
        ("w_out", P(ep_axis, tp_axis, None)),
    ]


def collect_aux_loss(intermediates, weight: float = 0.01):
    """Sum every sown ``moe_aux_loss`` in an intermediates tree."""
    total = 0.0
    n = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        if any(
            getattr(k, "key", None) == "moe_aux_loss" for k in path
        ):
            total = total + jnp.sum(jnp.asarray(leaf))
            n += 1
    return weight * total if n else jnp.asarray(0.0)


def collect_route_stats(intermediates):
    """Every sown ``route_stats`` of an intermediates tree as one int32
    ``[n_expert_layers, 3]`` (a scanned stack's come stacked on its
    layer axis), or None where no drop-free expert layer ran."""
    found = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        if any(getattr(k, "key", None) == "route_stats" for k in path):
            found.append(jnp.asarray(leaf, jnp.int32).reshape(-1, 3))
    return jnp.concatenate(found) if found else None
