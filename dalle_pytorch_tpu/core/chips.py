"""Published per-chip peaks, keyed by the `device_kind` string JAX reports.

The package's ONE hardware table: MFU (training/profiling.py), the HBM
fits-verdict (observability/memory.py) and the comms roofline
(observability/comms.py) all price against it.  Sources: Google Cloud TPU
documentation, the "TPU v4" / "TPU v5e" / "TPU v5p" / "TPU v6e" system
architecture pages (bf16 peak, HBM capacity, inter-chip interconnect
bandwidth per chip).

A device that is not in the table is an ERROR, not a default: a number
priced against the wrong peak is worse than no number.  On CPU there is no
peak to price against and `chip_spec` returns None — callers then print no
MFU / headroom / roofline figure at all."""
from __future__ import annotations

from typing import NamedTuple, Optional


class ChipSpec(NamedTuple):
    bf16_flops: float       # dense bf16 FLOP/s
    hbm_bytes: float        # HBM capacity, bytes
    ici_bytes_per_s: float  # aggregate chip-to-chip interconnect, bytes/s


_V5E = ChipSpec(197e12, 16e9, 200e9)
_V5P = ChipSpec(459e12, 95e9, 600e9)
_V6E = ChipSpec(918e12, 32e9, 450e9)

CHIPS = {
    "TPU v4": ChipSpec(275e12, 32e9, 300e9),
    "TPU v5 lite": _V5E,  # what a v5e reports
    "TPU v5e": _V5E,
    "TPU v5": _V5P,       # what a v5p reports
    "TPU v5p": _V5P,
    "TPU v6 lite": _V6E,  # what a v6e reports
    "TPU v6e": _V6E,
}


def chip_spec(device=None) -> Optional[ChipSpec]:
    """Peaks of `device` (default: the first local device).  None on CPU;
    raises ValueError for an accelerator the table does not know."""
    if device is None:
        import jax

        device = jax.local_devices()[0]
    if device.platform == "cpu":
        return None
    if device.device_kind not in CHIPS:
        raise ValueError(
            f"unknown accelerator device_kind {device.device_kind!r}: add its "
            f"published peaks to dalle_pytorch_tpu/core/chips.py "
            f"(known: {sorted(CHIPS)})"
        )
    return CHIPS[device.device_kind]
