"""Pallas TPU kernels for the framework's compute hot spots.

* ``flash_attention`` — GQA flash attention (causal / windowed) forward.
* ``ssd``             — Mamba2 chunked state-space-duality scan.
* ``wastage``         — KS+ fleet-scale wastage evaluation.

Each kernel ships ``kernel.py`` (pl.pallas_call + BlockSpec VMEM tiling),
``ops.py`` (jit'd wrapper) and ``ref.py`` (pure-jnp oracle used by the
allclose test sweeps).  The ``wastage`` wrappers run compiled unless the
caller passes ``interpret=True``; the ``flash_attention`` and ``ssd``
wrappers switch to interpret mode off a TPU.
"""

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ssd.ops import ssd_pallas
from repro.kernels.wastage.ops import wastage_eval

__all__ = ["flash_attention", "ssd_pallas", "wastage_eval"]
