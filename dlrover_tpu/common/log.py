"""Single configured logger (parity: dlrover/python/common/log.py:33).

Multi-host attribution: once the JAX process index is known — from the
agent's ``DLROVER_TPU_PROCESS_ID`` env contract at import, or
:func:`set_process_index` after ``jax.distributed.initialize`` — every
line carries a ``[proc N]`` tag, so interleaved multi-host logs remain
attributable. ``DLROVER_TPU_LOG_JSON=1`` switches the handler to a
one-object-per-line JSON format for log shippers.
"""

import json
import logging
import os
import sys
import threading
from typing import Optional

_FORMAT = (
    "[%(asctime)s] [%(levelname)s] "
    "[%(filename)s:%(lineno)d]%(proc_tag)s %(message)s"
)

#: reentrant: a span's record asks for the index, and the collector's
#: hook (telemetry/tracing.py) can write a span from inside any
#: allocation of the thread it interrupts, this lock's holder included
_proc_lock = threading.RLock()
_process_index: Optional[int] = None


def current_process_index() -> Optional[int]:
    """The JAX process index of this process, or None before it is
    known. Never touches jax (a logging/telemetry path must not trigger
    backend init): the agent's env contract seeds it, and
    ``set_process_index`` updates it after distributed init."""
    global _process_index
    with _proc_lock:
        if _process_index is None:
            raw = os.getenv("DLROVER_TPU_PROCESS_ID", "")
            if raw.strip().lstrip("-").isdigit():
                _process_index = int(raw)
        return _process_index


def set_process_index(index: int) -> None:
    """Record the distributed process index (called by
    ``trainer.distributed.init_from_env`` once the real value exists)."""
    global _process_index
    with _proc_lock:
        _process_index = int(index)


class _ProcTagFilter(logging.Filter):
    """Injects ``proc_tag`` (e.g. `` [proc 2]``) into every record."""

    def filter(self, record: logging.LogRecord) -> bool:
        idx = current_process_index()
        record.proc_tag = "" if idx is None else f" [proc {idx}]"
        return True


class _JsonFormatter(logging.Formatter):
    """One JSON object per line (opt-in: DLROVER_TPU_LOG_JSON=1)."""

    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": self.formatTime(record),
            "level": record.levelname,
            "file": record.filename,
            "line": record.lineno,
            "proc": current_process_index(),
            "msg": record.getMessage(),
        }
        if record.exc_info:
            out["exc"] = self.formatException(record.exc_info)
        return json.dumps(out, default=str)


def _build_logger() -> logging.Logger:
    logger = logging.getLogger("dlrover_tpu")
    if logger.handlers:
        return logger
    level = os.getenv("DLROVER_TPU_LOG_LEVEL", "INFO").upper()
    logger.setLevel(level)
    handler = logging.StreamHandler(stream=sys.stderr)
    if os.getenv("DLROVER_TPU_LOG_JSON", "") == "1":
        handler.setFormatter(_JsonFormatter())
    else:
        handler.setFormatter(logging.Formatter(_FORMAT))
    handler.addFilter(_ProcTagFilter())
    logger.addHandler(handler)
    logger.propagate = False
    return logger


default_logger = _build_logger()
