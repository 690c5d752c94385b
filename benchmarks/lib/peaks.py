"""Published peaks per chip, keyed by ``device_kind``, and the functions
that count the operations a program needs from its shapes.

A ``device_kind`` that is not in :data:`PEAKS` is an error, never a
default: a roofline share against the wrong chip's peak is a wrong
number with a right name.

Sources: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
ICI per chip), via /opt/skills/guides/on-chip-measurement section 4.
The table and :func:`resnet18_step_flops_per_image` are copied from
``bench.py`` (``_PEAK_BF16``, ``_STEP_FLOPS_PER_IMAGE``); the original is
listed in PERF.md "Open questions" for a later PR to delete.
"""

from __future__ import annotations

from typing import Dict

#: per chip: dense bf16 FLOP/s, HBM bytes/s, HBM bytes
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            "benchmarks/lib/peaks.py with its source before measuring on it"
        ) from None


def resnet18_step_flops_per_image() -> float:
    """Analytic CIFAR ResNet18 training-step FLOPs per image with EVERY
    parameter trainable: forward ~0.56 GMAC (3x3 stem at 32x32: 1.8 MMAC;
    layer1 4 x 3x3x64x64 at 32x32: 151 MMAC; layers 2-4 ~134 MMAC each
    after the stride-2 downsamples), a step ~3 x forward (forward + two
    backward products) at 2 FLOPs per MAC.  Honest only for a full-net
    epoch: a masked block prunes its backward, so no cell of today uses
    it (PERF.md, further metrics: ``mfu_pct``)."""
    return 3 * 2 * 0.56e9
