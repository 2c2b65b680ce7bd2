"""Backend-aware Pallas execution-mode policy (shared by every kernel).

One question, answered in one place: should a ``pallas_call`` run compiled
(TPU) or in interpret mode (CPU/GPU hosts where Mosaic cannot lower)?

The platform decides: compiled on TPU, interpreted elsewhere.  A call site
may pass ``interpret=True`` explicitly (the CPU tests do), but never on a
TPU backend — there the request is refused, so a kernel can never run
interpreted on the chip it was written for.

Kernel modules default their ``interpret`` parameter to ``None`` and call
``resolve_interpret`` so a bare ``lora_matmul(...)`` does the right thing on
both the CPU test host and real TPU hardware without any plumbing.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_default() -> bool:
    """True when Pallas kernels run in interpret mode: every non-TPU backend."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The platform default, or an explicit call-site choice off-TPU."""
    if interpret is None:
        return interpret_default()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "Pallas interpret mode requested on a TPU backend; kernels run "
            "compiled on TPU (pass interpret=None)"
        )
    return bool(interpret)
