"""Readers of the training cell's trace: each device's executions of the
jitted step, its flash kernel calls and its collectives."""

from benchmarks.common import flash_call_cost, reader


def _per_device(facts):
    tr = facts.get("trace")
    return tr["per_device"] if tr and tr.get("per_device") else None


def _mean(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def _step_s(facts):
    devs = _per_device(facts)
    return devs and _mean(p["end"] - p["start"]
                          for d in devs for p in d["steps"])


@reader("train_step_ms")
def train_step_ms(facts, params):
    x = _step_s(facts)
    return x and x * 1e3


@reader("train_step_gap_ms")
def train_step_gap_ms(facts, params):
    """Device idle from the end of one step's program to the start of the
    next one's, mean over devices and steps."""
    devs = _per_device(facts)
    if not devs:
        return None
    x = _mean(b["start"] - a["end"] for d in devs
              for a, b in zip(d["steps"], d["steps"][1:]))
    return None if x is None else x * 1e3


@reader("train_mfu")
def train_mfu(facts, params):
    s = facts["scalars"]
    return 100.0 * s["flops_per_token"] * s["tokens_per_s"] / (
        s["chips"] * facts["peaks"]["flops_bf16"])


@reader("collective_exposed_share")
def collective_exposed_share(facts, params):
    """The device's operation line runs one operation at a time: while a
    collective's operation (an all-reduce, or the ``-done`` of an
    asynchronous one) is on it, no compute is. Their time over the steps'
    time."""
    devs = _per_device(facts)
    if not devs:
        return None
    steps = sum(p["end"] - p["start"] for d in devs for p in d["steps"])
    return steps and 100.0 * sum(d["collective_s"] for d in devs) / steps


def flash_kind(result: str) -> str:
    """Which flash kernel a ``tpu_custom_call`` is, from what it returns
    (its instruction name, ``shard_map.380``, changes with every compile):
    forward returns (o, lse), an f32 among them; dk/dv returns two bf16
    tensors; dq returns one."""
    if not result.startswith("("):
        return "dq"
    return "fwd" if "f32[" in result else "dkv"


@reader("flash_roofline_share")
def flash_roofline_share(facts, params):
    """For the flash kernel calls the trace shows (forward, dq, dk/dv; a
    forward recomputed in the backward pass is a call like any other): the
    least time the chip could take, max(FLOPs / peak, bytes / bandwidth)
    per call, over the time they took. Which side bounds each kind goes
    into ``facts["note"]``."""
    devs = _per_device(facts)
    if not devs:
        return None
    t, dims, peaks = facts["train"], facts["model_dims"], facts["peaks"]
    tp = max(1, t["mesh"].get("tp", 1))
    dp = t["mesh"].get("dp", 1)
    if dp < 1:
        dp = facts["scalars"]["chips"] // tp
    bh = (t["global_batch"] // dp) * (dims["n_heads"] // tp)
    least = took = 0.0
    bound, calls = {}, {}
    for d in devs:
        for o in d["kernel_ops"]:
            kind = flash_kind(o["result"])
            cost = flash_call_cost(kind, bh, t["seq"], dims["d_head"])
            by_flops = cost["flops"] / peaks["flops_bf16"]
            by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
            bound[kind] = "flops" if by_flops >= by_bytes else "bytes"
            calls[kind] = calls.get(kind, 0) + 1
            least += max(by_flops, by_bytes)
            took += o["s"]
    facts.setdefault("note", {}).update(flash_bound_by=bound,
                                        flash_calls=calls)
    return took and 100.0 * least / took
