"""Mixed precision — the TPU answer to ``torch.cuda.amp`` (BASELINE.json:5,9).

TPUs compute natively in bfloat16, whose exponent range equals float32's —
so the loss-scaling dance CUDA AMP exists for (fp16 underflow) is
unnecessary. The idiomatic policy is therefore:

* parameters + optimizer state in float32,
* matmul/conv inputs cast to bfloat16 (MXU-native),
* loss/reductions in float32.

For recipe-script parity we keep the AMP API shape:

* :func:`autocast` — context manager that sets the active compute dtype;
  model code reads ``current_policy().compute_dtype``.
* :class:`GradScaler` — ``scale`` / ``unscale`` / ``step``-compatible. In
  bf16 mode it is an exact no-op (scale == 1.0, never skips steps). If
  constructed with ``dtype=float16`` it performs real dynamic loss scaling
  (functional update usable inside a jitted step).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Optional

import jax
import jax.extend.core as jcore
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Policy:
    """Dtype policy applied by models and the train step."""

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    output_dtype: Any = jnp.float32

    def cast_to_compute(self, tree):
        return _cast_floating(tree, self.compute_dtype)

    def cast_to_param(self, tree):
        return _cast_floating(tree, self.param_dtype)

    def cast_to_output(self, tree):
        return _cast_floating(tree, self.output_dtype)

    def to_output(self, x):
        """``x`` in ``output_dtype`` holding ``x.dtype``'s values still.

        A bare ``astype`` to a wider dtype leaves the compiler free to
        skip the narrow rounding when it fuses the cast with the product
        that made ``x`` (XLA's excess precision hands on the product's
        f32 accumulator; seen on a v5e at Mistral's head, PERF.md §6
        PR 33), and a greedy token at a bf16 tie then follows a fusion
        choice. ``reduce_precision`` at ``x.dtype``'s own bits pins the
        rounding; between equal dtypes it is the identity."""
        stated = jnp.finfo(x.dtype)
        return jax.lax.reduce_precision(
            x.astype(self.output_dtype), stated.nexp, stated.nmant
        )


def _cast_floating(tree, dtype):
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(cast, tree)


def rest_in_compute_dtype(forward, params, dtype):
    """``params`` with every leaf the model only ever reads in ``dtype``
    stored in ``dtype``, and ``{"leaves": n, "bytes": [before, after]}``
    of what was cast.

    A leaf is cast when it is a float wider than ``dtype`` and every use
    of it in ``forward(params)`` is a ``convert_element_type`` to
    ``dtype`` (a ``Dense`` kernel under the default :class:`Policy`):
    cast once here, it is the very operand the program made of it on
    every call — the same bits, without the per-call read of the wide
    copy. A leaf read wide anywhere (an embedding lookup, a norm scale)
    is left as it was. ``forward`` is traced once over abstract shapes,
    and not at all when no leaf is wider than ``dtype``; when nothing is
    cast, ``params`` itself comes back. Leaves may be arrays or
    ``jax.ShapeDtypeStruct``\\ s (a compile over shapes alone)."""
    dtype = jnp.dtype(dtype)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    wide = [
        i for i, x in enumerate(leaves)
        if jnp.issubdtype(x.dtype, jnp.floating)
        and jnp.dtype(x.dtype).itemsize > dtype.itemsize
    ]
    if wide:
        closed = jax.make_jaxpr(
            lambda *ls: forward(jax.tree_util.tree_unflatten(treedef, ls))
        )(*(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves))
        narrowed = _only_narrowed(closed.jaxpr, dtype, {})
        wide = [i for i in wide if narrowed[i]]
    picked = [leaves[i] for i in wide]
    cast = {"leaves": len(picked), "bytes": [
        sum(x.size * jnp.dtype(x.dtype).itemsize for x in picked),
        sum(x.size * dtype.itemsize for x in picked),
    ]}
    if not picked:
        return params, cast
    if all(isinstance(x, jax.ShapeDtypeStruct) for x in picked):
        narrow = [
            jax.ShapeDtypeStruct(x.shape, dtype, sharding=x.sharding)
            for x in picked
        ]
    else:
        narrow = _astype(picked, dtype)
    for i, x in zip(wide, narrow):
        leaves[i] = x
    return jax.tree_util.tree_unflatten(treedef, leaves), cast


_astype = jax.jit(
    lambda xs, dtype: [x.astype(dtype) for x in xs], static_argnums=1
)

# operations whose sub-jaxpr takes the operation's operands in order
_CALLS = ("scan", "jit", "remat2", "closed_call", "custom_jvp_call",
          "custom_vjp_call")


def _only_narrowed(jaxpr, dtype, seen):
    """Per input of ``jaxpr``: is every use a convert to ``dtype``,
    followed by position into the sub-jaxprs of :data:`_CALLS` and of a
    ``cond``'s branches? Any other use, a return among them, says no."""
    ok = {v: True for v in jaxpr.invars}
    for v in jaxpr.outvars:
        if v in ok:
            ok[v] = False
    for eqn in jaxpr.eqns:
        for pos, v in enumerate(eqn.invars):
            if isinstance(v, jcore.Literal) or not ok.get(v):
                continue
            if (eqn.primitive.name == "convert_element_type"
                    and eqn.params["new_dtype"] == dtype):
                continue
            subs = _subjaxprs(eqn)
            ok[v] = bool(subs) and all(
                pos >= skip and _sub_narrowed(sub, dtype, seen)[pos - skip]
                for sub, skip in subs
            )
    return [ok[v] for v in jaxpr.invars]


def _subjaxprs(eqn):
    """[(sub-jaxpr, how many leading operands it does not take)]."""
    name = eqn.primitive.name
    if name == "cond":
        return [(b.jaxpr, 1) for b in eqn.params["branches"]]
    if name not in _CALLS:
        return []
    subs = [
        getattr(p, "jaxpr", p) for p in eqn.params.values()
        if isinstance(p, (jcore.ClosedJaxpr, jcore.Jaxpr))
    ]
    return [(s, 0) for s in subs[:1] if len(s.invars) == len(eqn.invars)]


def _sub_narrowed(jaxpr, dtype, seen):
    if id(jaxpr) not in seen:
        seen[id(jaxpr)] = _only_narrowed(jaxpr, dtype, seen)
    return seen[id(jaxpr)]


_FULL = Policy(compute_dtype=jnp.float32)
_STATE = threading.local()


def current_policy() -> Policy:
    return getattr(_STATE, "policy", Policy())


def autocast(enabled: bool = True, dtype=jnp.bfloat16):
    """AMP-shaped context manager selecting the compute dtype.

    Unlike torch autocast this does not intercept ops — models consult
    ``current_policy()`` at *trace* time, so wrap the jit/trace site
    (building the train step), not the runtime step call.
    """
    return use_policy(Policy(compute_dtype=dtype) if enabled else _FULL)


@contextlib.contextmanager
def use_policy(policy: Policy):
    """Install an explicit dtype :class:`Policy` at trace time —
    ``autocast``'s general form. The serving case that needs it:
    ``scan_dequant`` reconstructs each quantized layer at
    ``current_policy().param_dtype`` (models/scan.py), so decoding a
    big model under ``Policy(param_dtype=bfloat16)`` halves both the
    per-layer transient and the HBM reads vs the f32 default."""
    prev = getattr(_STATE, "policy", None)
    _STATE.policy = policy
    try:
        yield policy
    finally:
        if prev is None:
            del _STATE.policy
        else:
            _STATE.policy = prev


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScalerState:
    """Functional dynamic-loss-scale state (fp16 mode only). A pytree, so
    it can be carried through jitted train steps."""

    scale: jnp.ndarray
    growth_tracker: jnp.ndarray


class GradScaler:
    """``torch.cuda.amp.GradScaler``-compatible surface.

    bf16 (default): everything is the identity and ``update`` never skips —
    recipes keep their AMP scaffolding with zero cost.

    fp16: real dynamic scaling. Use the functional triple inside a jitted
    step::

        loss = scaler.scale_value(loss, state)
        grads = scaler.unscale_grads(grads, state)
        state, ok = scaler.functional_update(grads, state)   # ok: apply step?
    """

    def __init__(
        self,
        init_scale: float = 2.0**15,
        growth_factor: float = 2.0,
        backoff_factor: float = 0.5,
        growth_interval: int = 2000,
        enabled: bool = True,
        dtype=jnp.bfloat16,
    ):
        self.enabled = enabled and jnp.dtype(dtype) == jnp.float16
        self.init_scale = init_scale
        self.growth_factor = growth_factor
        self.backoff_factor = backoff_factor
        self.growth_interval = growth_interval

    def init_state(self) -> Optional[ScalerState]:
        if not self.enabled:
            return None
        return ScalerState(
            scale=jnp.float32(self.init_scale),
            growth_tracker=jnp.int32(0),
        )

    # -- functional (in-jit) API -------------------------------------------
    def scale_value(self, loss, state: Optional[ScalerState]):
        if not self.enabled or state is None:
            return loss
        return loss * state.scale

    def unscale_grads(self, grads, state: Optional[ScalerState]):
        if not self.enabled or state is None:
            return grads
        inv = 1.0 / state.scale
        return jax.tree_util.tree_map(lambda g: g * inv, grads)

    def functional_update(self, grads, state: Optional[ScalerState]):
        """Returns (new_state, grads_finite). Callers skip the optimizer
        step (lax.cond / jnp.where) when grads_finite is False."""
        if not self.enabled or state is None:
            return state, jnp.bool_(True)
        leaves = jax.tree_util.tree_leaves(grads)
        finite = jnp.bool_(True)
        for leaf in leaves:
            finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(leaf)))
        tracker = jnp.where(finite, state.growth_tracker + 1, 0)
        grow = tracker >= self.growth_interval
        scale = jnp.where(
            finite,
            jnp.where(grow, state.scale * self.growth_factor, state.scale),
            state.scale * self.backoff_factor,
        )
        tracker = jnp.where(grow, 0, tracker)
        return ScalerState(scale=scale, growth_tracker=tracker), finite

    # -- torch-API-shaped eager conveniences -------------------------------
    # Valid only in bf16 mode, where scaling is genuinely the identity. In
    # fp16 mode the state lives in the (functional) train step, so the
    # stateful torch surface would silently drop the scaling — refuse it.
    def _eager_ok(self):
        if self.enabled:
            raise RuntimeError(
                "fp16 GradScaler state is functional: use scale_value/"
                "unscale_grads/functional_update inside the train step "
                "(the eager torch-shaped methods are only exact in bf16 mode)"
            )

    def scale(self, loss):
        self._eager_ok()
        return loss

    def unscale_(self, grads):
        self._eager_ok()
        return grads

    def step(self, apply_fn, *args, **kwargs):
        self._eager_ok()
        return apply_fn(*args, **kwargs)

    def update(self):
        self._eager_ok()
        return None

    def get_scale(self) -> float:
        self._eager_ok()
        return 1.0
