"""Model-layout wrapper for the SSD scan kernel."""

from __future__ import annotations

from .kernel import ssd_scan_fwd

__all__ = ["ssd_scan"]


def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, interpret: bool = False):
    """x: (b,s,h,p); dt: (b,s,h); A: (h,); B, C: (b,s,n).  Returns (b,s,h,p).

    Always the Pallas kernel; ``interpret=True`` runs it in interpret mode.
    The jnp oracle is :func:`repro.kernels.ssd_scan.ref.ssd_scan_ref`."""
    y = ssd_scan_fwd(
        x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A, B, C,
        chunk=chunk, interpret=interpret,
    )
    return y.transpose(0, 2, 1, 3)
