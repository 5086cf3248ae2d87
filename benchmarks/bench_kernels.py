"""Pallas tile-kernel microbench: per-call time + arithmetic intensity.

Wall time here is the *interpret-mode* (CPU) figure — meaningful only for
relative tracking. The derived column reports the kernel's FLOPs and the
VMEM tile-resident bytes/ratio used by the TPU roofline discussion in
DESIGN.md §3 (memory model).
"""
from __future__ import annotations

import numpy as np

from repro.kernels import pairwise_count, pairwise_minlabel
from repro.kernels.ref import pairwise_count_ref
from repro.data import pointclouds
from .common import emit, time_fn


def run(quick: bool = False):
    for n in ([1024] if quick else [1024, 4096]):
        pts = pointclouds.load("portotaxi_like", n)
        eps = 0.01
        # difference form: n^2 x (d subs + d muls + d-1 adds + 1 compare)
        flops = n * n * (3 * 2)
        tile_bytes = 128 * 2 * 4 + 128 * 2 * 4 + 128 * 128 * 4
        dt, _ = time_fn(pairwise_count, pts, pts, eps,
                        warmup=1, repeat=1 if quick else 3)
        emit(f"kernel/count/n{n}", dt * 1e6,
             f"flops={flops};tile_vmem_bytes={tile_bytes}")
        labels = np.arange(n, dtype=np.int32)
        mask = np.ones(n, bool)
        dt, _ = time_fn(pairwise_minlabel, pts, pts, labels, mask, eps,
                        warmup=1, repeat=1 if quick else 3)
        emit(f"kernel/minlabel/n{n}", dt * 1e6,
             f"flops={flops};tile_vmem_bytes={tile_bytes}")
        dt, _ = time_fn(pairwise_count_ref, pts, pts, eps,
                        warmup=1, repeat=1 if quick else 3)
        emit(f"kernel/count-jnp-ref/n{n}", dt * 1e6, "reference")


if __name__ == "__main__":
    run()
