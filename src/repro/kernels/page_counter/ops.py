"""Dispatch wrapper for the two-stage counter."""
from __future__ import annotations

import jax

from repro.kernels.page_counter.page_counter import (
    fused_observe_count,
    two_stage_count,
)
from repro.kernels.page_counter.ref import (
    fused_observe_count_ref,
    two_stage_count_ref,
)


def _kernel_mode(force) -> str:
    return force or ("pallas" if jax.default_backend() == "tpu" else "ref")


def count_accesses(
    sp, page, weight, monitored, num_superpages, pages_per_sp, force=None
):
    mode = _kernel_mode(force)
    if mode in ("pallas", "interpret"):
        return two_stage_count(
            sp, page, weight, monitored, num_superpages, pages_per_sp,
            interpret=(mode == "interpret"),
        )
    return two_stage_count_ref(
        sp, page, weight, num_superpages, monitored, pages_per_sp
    )


def observe_counts(
    sp, page, is_write, monitored, num_superpages, pages_per_sp,
    write_weight=2, force=None,
):
    """Fused one-pass observe histograms: (s1, s2_reads, s2_writes).

    The MemoryEngine's counting step (engine.control.observe_tiers) dispatches
    here when `counter_backend` != "jax": "pallas" compiles the kernel for the
    TPU, "interpret" runs it in the Pallas interpreter, "ref" is the pure-jnp
    oracle.
    """
    mode = _kernel_mode(force)
    if mode in ("pallas", "interpret"):
        return fused_observe_count(
            sp, page, is_write, monitored, num_superpages, pages_per_sp,
            write_weight=write_weight, interpret=(mode == "interpret"),
        )
    return fused_observe_count_ref(
        sp, page, is_write, monitored, num_superpages, pages_per_sp,
        write_weight=write_weight,
    )
