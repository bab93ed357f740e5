"""What the algorithm needs, from the configuration alone, and the chip's
peaks.

The counts themselves belong to the configuration's family module
(``bench/families/<family>.py``); the functions here hand each call to
it.  They count what a step must do, not what a compiled program happens
to do, so they read the same whatever implements the step.
"""

from __future__ import annotations

import pathlib
from typing import Sequence

from bench import model, spec

#: published peaks per ``device_kind`` (Google Cloud documentation, "TPU
#: v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip)
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def kv_bytes_per_token(cfg: dict, root: pathlib.Path = spec.ROOT) -> int:
    """The cache one token holds, over every layer."""
    return model.family(cfg, root).kv_bytes_per_token(cfg)


def decode_bytes(cfg: dict, executions: int, contexts: Sequence[int],
                 root: pathlib.Path = spec.ROOT) -> int:
    """Bytes that ``executions`` decode executions need, given the filled
    context (the new token's included) of every row-step in them."""
    return model.family(cfg, root).decode_bytes(cfg, executions, contexts)


def token_flops(cfg: dict, context: int, head: bool = True,
                root: pathlib.Path = spec.ROOT) -> int:
    """Operations to push one token through the model attending to
    ``context`` positions; the head's only where ``head``."""
    return model.family(cfg, root).token_flops(cfg, context, head)


def prefill_flops(cfg: dict, prompt: int,
                  root: pathlib.Path = spec.ROOT) -> int:
    """Operations of one prompt's prefill."""
    return model.family(cfg, root).prefill_flops(cfg, prompt)
