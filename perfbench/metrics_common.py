"""Arithmetic shared by the metric readers (``metrics/``)."""

from perfbench import harness, trace_reduce


def read_as(metric: str):
    """The ``read`` of ``metrics/<metric>.py``: a metric split by the
    end-to-end metric it moves reads what the one it splits reads."""
    return harness._module(harness.BENCH / "metrics" / f"{metric}.py",
                           f"perfbench_metric_{metric}").read


def roofline_s(calls, peaks: dict) -> float:
    """Least seconds for ``(flops, bytes)`` calls on a chip with these
    peaks: per call the larger of operations over the bf16 peak and
    bytes over the HBM peak."""
    return sum(max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
               for f, b in calls)


def least_s(ctx, calls) -> float:
    """Least seconds of one step's ``calls`` of a kernel summed over the
    chips: ``calls`` are what each chip makes (``models.work``), and a
    trace's kernel time sums every chip's events."""
    return ctx.chips * roofline_s(calls, ctx.peaks)


def roofline_share(ctx, kernel: str, pattern: str):
    """% of its roofline the kernel reached over the traced window: the
    least time of the calls the model says a step makes through it on
    each chip, over the device time of the kernel's events on all chips.
    None (nothing to read) where the trace does not hold exactly those
    calls, one event each per step and chip
    (``steps × len(calls) × chips``): the kernel is then off the path, or
    the calls it makes are not the ones whose work is counted."""
    calls = ctx.work["kernels"].get(kernel)
    if ctx.trace is None or ctx.peaks is None or not calls:
        return None
    events = ctx.steps * len(calls) * ctx.chips
    if trace_reduce.kernel_calls(ctx.trace, pattern) != events:
        return None
    spent = trace_reduce.kernel_s(ctx.trace, pattern)
    return 100.0 * ctx.steps * least_s(ctx, calls) / spent
