"""The yardstick's small parts: the table of peaks, the arithmetic of the
metrics, the operations and bytes a kernel's call needs, and the registry
by which data files name a function.

Kept under ``benchmarks/`` so that no later PR that claims a gain can
change how a number is computed. Nothing here imports JAX.
"""

from __future__ import annotations

import importlib.util
import math
import os
import statistics
from typing import Callable, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GIB = 2 ** 30
REHEARSAL_RC = 10  # the host walked the control flow: never a result

# One chip. Source: Google Cloud documentation, "TPU v5e" system
# architecture: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip. Keyed by
# ``jax.devices()[0].device_kind``; a kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class BenchFailure(Exception):
    """The run cannot give a result: wrong device, failed phase."""


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise BenchFailure(
            f"device_kind {device_kind!r} is not in benchmarks/common.py "
            f"PEAKS ({sorted(PEAKS)}): add it with its source"
        )
    return PEAKS[device_kind]


# -- statistics --------------------------------------------------------------

def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def tpot_ms(token_times: Sequence[float], min_tokens: int = 16
            ) -> Optional[float]:
    """One request's time per output token after the first, in ms:
    ``(t_last - t_first) / (n - 1)``; None for fewer than ``min_tokens``."""
    n = len(token_times)
    if n < min_tokens or n < 2:
        return None
    return (token_times[-1] - token_times[0]) / (n - 1) * 1e3


def quartile_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median with ``statistics.quantiles(n=4)``: the spread
    the builder's instructions define."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


# -- operations and bytes from shapes ----------------------------------------

def layer_matmul_params(c: Dict) -> int:
    """Weights of one block that a token multiplies: q, k, v, o, MLP in
    and out. ``c`` holds d_model, n_heads, d_head, d_ff."""
    d, h, k, f = c["d_model"], c["n_heads"], c["d_head"], c["d_ff"]
    return 4 * d * h * k + 2 * d * f


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward + backward of a causal decoder, recomputation NOT counted:
    6 per matmul weight (2 forward, 4 backward) over the blocks and the
    output head (the embedding is a gather), plus attention's two
    batched matmuls (QK^T and PV): 2 * 2 * S * H * Dh forward per token on
    a full square, halved for the causal triangle, times 3 for
    forward + backward."""
    matmul = c["n_layers"] * layer_matmul_params(c) + \
        c["d_model"] * c["vocab_size"]
    attn = c["n_layers"] * 3 * 0.5 * 4 * seq * c["n_heads"] * c["d_head"]
    return 6.0 * matmul + attn


def flash_call_cost(kind: str, bh: int, s: int, d: int,
                    itemsize: int = 2) -> Dict[str, float]:
    """Least operations and HBM bytes of ONE call of a causal flash
    kernel over [bh, s, d]. Matmuls on the causal triangle (half the
    square): fwd 2 (QK^T, PV); dq 3 (QK^T, dO V^T, dS K); dkv 4 (QK^T,
    dO V^T, P^T dO, dS^T Q). Each is 2*s*s*d FLOPs per head on the square.
    Bytes: every operand read once and every result written once."""
    n_mm = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    n_in = {"fwd": 3, "dq": 4, "dkv": 4}[kind]
    n_out = {"fwd": 1, "dq": 1, "dkv": 2}[kind]
    vec = {"fwd": 1, "dq": 2, "dkv": 2}[kind]  # lse (+ delta), f32 [bh, s]
    return {
        "flops": 0.5 * n_mm * 2.0 * bh * s * s * d,
        "bytes": float((n_in + n_out) * bh * s * d * itemsize
                       + vec * bh * s * 4),
    }


def decode_step_bytes(c: Dict, kv_rows: float) -> float:
    """Bytes ONE decode step must read: every block's int8 weights and
    their float32 scales, the bf16 output head, the final norm, and the
    valid K and V rows of the live slots (``kv_rows`` = sum over live
    slots of tokens already in the cache), bf16. The embedding is a gather
    of a few rows and is left out."""
    d, h, k, f, L = (c["d_model"], c["n_heads"], c["d_head"], c["d_ff"],
                     c["n_layers"])
    weights = L * layer_matmul_params(c)  # one byte each
    scales = L * 4 * (3 * h * k + d + f + d)
    norms = 2 * (L + 1) * d
    head = 2 * d * c["vocab_size"]
    kv = kv_rows * L * 2 * c.get("n_kv_heads", h) * k * 2
    return float(weights + scales + norms + head + kv)


# -- registry ----------------------------------------------------------------

READERS: Dict[str, Callable] = {}
GENERATORS: Dict[str, Callable] = {}


def reader(name: str):
    def deco(fn):
        READERS[name] = fn
        return fn
    return deco


def generator(name: str):
    def deco(fn):
        GENERATORS[name] = fn
        return fn
    return deco


def load_plugins(*bench_dirs: str) -> None:
    """Import every ``readers/<name>.py`` and ``generators/<name>.py``
    under the given benchmark directories: each registers itself with the
    decorators above. A later PR adds a file; none is edited."""
    seen = set()
    for base in bench_dirs:
        for sub in ("readers", "generators"):
            folder = os.path.join(base, sub)
            if not os.path.isdir(folder):
                continue
            for fname in sorted(os.listdir(folder)):
                path = os.path.join(folder, fname)
                if not fname.endswith(".py") or fname.startswith("_") \
                        or os.path.realpath(path) in seen:
                    continue
                seen.add(os.path.realpath(path))
                spec = importlib.util.spec_from_file_location(
                    f"benchmarks_plugin_{sub}_{fname[:-3]}", path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)


def prepare_env(rehearse_cpu: bool) -> None:
    """Before any cluster starts. The compile cache goes where the
    environment says, else to a fixed path inside this checkout (the
    program's own default); nothing sets it in code. ``JAX_PLATFORMS`` is
    cleared so that the rule at worker spawn decides: the TPU worker gets
    the chips or JAX's error, never the host; a rehearsal pins the host."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        os.environ.pop("JAX_PLATFORMS", None)


def seed_words(seed: int) -> List[int]:
    """``--seed`` may be a little over 2**31: two words that fit int32."""
    seed = int(seed)
    return [seed & 0x7FFFFFFF, seed >> 31]
