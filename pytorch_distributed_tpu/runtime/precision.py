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
