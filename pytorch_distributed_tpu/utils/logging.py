"""Rank-0 structured logging.

Single-controller SPMD has one process per host; only the first host
(process_index 0) should emit training logs — the analogue of the
reference recipes' ``if rank == 0: print(...)`` gating.

The rank check is deferred to the first *emitted* record (via a logging
filter), not done at ``get_logger`` time: modules create loggers at import,
and resolving ``jax.process_index()`` there would initialize the backend as
an import side effect — a chip belongs to one process at a time, so a
launcher parent that merely imports this package would take the chip from
the child it is about to start.
"""

from __future__ import annotations

import logging
import sys

_CONFIGURED = False


class _Rank0Filter(logging.Filter):
    """Drop records on non-zero hosts; resolve the rank lazily per record.

    The answer is only cached once ``jax.distributed`` is initialized (or
    provably single-process): before that, ``jax.process_index()`` returns
    0 on *every* host, and caching that early answer would permanently
    disable the gate on non-zero hosts for records emitted during setup.
    """

    _is_rank0 = None

    def filter(self, record: logging.LogRecord) -> bool:
        if _Rank0Filter._is_rank0 is not None:
            return _Rank0Filter._is_rank0
        from pytorch_distributed_tpu.runtime import device as _device

        is_rank0 = _device.process_index() == 0
        try:
            from jax._src import distributed as _jdist

            multihost_settled = _jdist.global_state.client is not None
        except Exception:  # pragma: no cover - jax internals moved
            multihost_settled = True
        if multihost_settled or _device.process_count() > 1:
            _Rank0Filter._is_rank0 = is_rank0
        return is_rank0


def _configure_root() -> None:
    global _CONFIGURED
    if _CONFIGURED:
        return
    handler = logging.StreamHandler(sys.stderr)
    handler.setFormatter(
        logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
    )
    # on the HANDLER, not the logger: logger-level filters don't see
    # records propagated up from child loggers, handler filters do
    handler.addFilter(_Rank0Filter())
    root = logging.getLogger("pytorch_distributed_tpu")
    root.addHandler(handler)
    root.setLevel(logging.INFO)
    root.propagate = False
    _CONFIGURED = True


def get_logger(name: str) -> logging.Logger:
    """Logger that is silent on non-zero hosts (decided at first emit)."""
    _configure_root()
    logger = logging.getLogger(name)
    if name.split(".")[0] != "pytorch_distributed_tpu" and not any(
        isinstance(f, _Rank0Filter) for f in logger.filters
    ):
        # out-of-namespace loggers (recipe code) don't route through the
        # namespace handler above — gate them at the logger itself
        logger.addFilter(_Rank0Filter())
    return logger


def log_rank0(msg: str, *args) -> None:
    get_logger("pytorch_distributed_tpu").info(msg, *args)
