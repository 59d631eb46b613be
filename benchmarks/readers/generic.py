"""Readers that a ``layer_metrics/<name>.json`` names with parameters.
A reader takes the run's facts (``samples``, ``scalars``, ``trace``,
``model_dims``, ``peaks``, ...) and the file's ``params``; it returns a
number, or None when there is nothing to read (the metric is then left
out of the line)."""

from benchmarks.common import GIB, percentile, reader


@reader("samples_percentile")
def samples_percentile(facts, params):
    xs = facts["samples"].get(params["samples"])
    return percentile(xs, params["q"]) if xs else None


@reader("scalar_ratio")
def scalar_ratio(facts, params):
    """``scale * num / den`` of two scalars (``one_minus``: of its
    complement); None where either is missing or the divisor is 0."""
    s = facts["scalars"]
    num, den = s.get(params["num"]), s.get(params["den"])
    if num is None or not den:
        return None
    x = num / den
    if params.get("one_minus"):
        x = 1.0 - x
    return params.get("scale", 1.0) * x


@reader("peak_hbm_gib")
def peak_hbm_gib(facts, params):
    x = facts["scalars"].get("peak_bytes")
    return None if not x else x / GIB


@reader("decode_hbm_share")
def decode_hbm_share(facts, params):
    """Bytes the traced decode steps had to read (common.decode_step_bytes)
    over their device time times the chip's HBM bandwidth."""
    s = facts["scalars"]
    if not s.get("decode_device_s"):
        return None
    return 100.0 * s["decode_bytes"] / (
        s["decode_device_s"] * facts["peaks"]["hbm_bytes_per_s"])
