"""Readers of ONE kernel's device time in the traced stretch, as a runner
summed it from the trace's operations beside what the kernel's calls had
to move (``facts["scalars"]``: a number of seconds and a number of bytes
under names the metric's file gives). Where the run has neither (a
program without the kernel, a runner that does not sum it) the reader
returns None and the metric is left out of the line."""

from benchmarks.common import reader


@reader("kernel_hbm_roofline_share")
def kernel_hbm_roofline_share(facts, params):
    """100 x the least time the chip could take to move ``bytes`` (over
    its HBM bandwidth) over the ``seconds`` the kernel's calls took."""
    s = facts["scalars"]
    moved, took = s.get(params["bytes"]), s.get(params["seconds"])
    if not moved or not took:
        return None
    return 100.0 * moved / (took * facts["peaks"]["hbm_bytes_per_s"])
