"""Ask the chip's compiler before the chip.

The TPU compiler is installed where the tests run and compiles for a chip
that is described, not attached (``jax.experimental.topologies``). The
flash kernel passes every interpret-mode test on CPU and can still be
refused by Mosaic — tiling, VMEM — so it is compiled here, uninterpreted,
at the widths the chip runs: ``bench_400m`` (b8 x 2048, 8 heads x 128) and
the long-context entry (b2 x 8192). Kernels only: nothing runs, and a
compile that passes is not a chip run.

What a cell serves is read where the benchmark states it: every served
program of this file is lowered from ``tools/lowered_texts.served``, which
builds the model as the cell's runner does from
``benchmarks/configs/<name>.json`` and sizes the cache and the lanes by its
``run.engine``. No slot count, row count, bucket or block length of a cell
is written here: a shape in a compiled text is spelt with ``{B}`` (the
file's ``max_slots``) and ``{S}`` (the rows a slot holds), and a program is
named by a bucket or a block length the file has, or is not lowered at all.
"""

import os
import re
from typing import Callable, NamedTuple, Optional, Tuple

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import flash_attention
from tools.lowered_texts import served


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    # Such a compile is written to the persistent cache but cannot be read
    # back without a chip: the next run would warn and compile again.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def as_on_the_chip():
    """Here the backend is the CPU, where a Pallas kernel would be
    interpreted: the programs of this file are compiled as the chip runs
    them."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        yield


def _copies(hlo: str, of: str):
    """``copy`` instructions of the compiled text whose result's type and
    leading dimensions are ``of`` (``"s8["``, ``"s8[28,"``)."""
    return re.findall(r"= " + re.escape(of) + r"[\d,]*\]\S* copy\(", hlo)


def _calls(hlo: str, *named: str):
    """The compiled text's kernel calls whose line holds every name."""
    return [line for line in hlo.splitlines()
            if "tpu_custom_call" in line and all(n in line for n in named)]


def _loss(q, k, v):
    out = flash_attention(q, k, v, interpret=False)
    return out.astype(jnp.float32).sum()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize(
    "shape", [(8, 2048, 8, 128), (2, 8192, 8, 128)], ids=["s2048", "s8192"]
)
def test_flash_kernel_compiles_for_v5e(v5e, shape, grad):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)
    fn = jax.grad(_loss, argnums=(0, 1, 2)) if grad else _loss
    hlo = jax.jit(fn).lower(x, x, x).compile().as_text()
    # fwd is one kernel; fwd+bwd adds the dq and the dk/dv kernels
    assert hlo.count("tpu_custom_call") == (3 if grad else 1)


# -- every served configuration's programs fit one v5e -----------------------

def _glm47_shows(hlo, program, B, S):
    """The served cut of GLM-4.7-Flash (8 layers, every width as published,
    bf16) at the benchmark's engine sizes. Mosaic has to take the experts'
    grouped product (``ops/grouped_matmul``: gate and up in one kernel,
    down in another) at 128 and at 8,192 rows, reading the stacked experts
    where they lie (ISSUE 31: no ``copy`` of a ``bf16[7,64,...]`` stack);
    the compiler has to take the walk over the latent cache (8 layers of
    576-wide rows), the latent cache has to be updated in place, and the
    program has to leave room on a 16 GB chip (ISSUE 28: under 14.5 GiB)."""
    assert (len(_calls(hlo, "raytpu.moe.experts")) == 2
            and "ragged-dot" not in hlo)
    if program.startswith("decode_block"):
        # ISSUE 33: the latent rows are read by the decode attention's
        # kernel (one call in the dense layer's stack, one in the routed
        # layers'), under the scope ``readers/scope_time.py`` looks for;
        # no chunk is copied into fast memory first, and no cache array
        # is laid out again (the rotary keys are read rows-minor, as the
        # chip keeps them)
        attends = _calls(hlo, "raytpu.mla.attend")
        assert len(attends) == 2
        assert all("decode_attention" in line for line in attends)
        assert "dynamic-slice_bitcast_fusion" not in hlo
        assert not _copies(hlo, f"bf16[8,{B},{S},")
    else:
        # ISSUE 59: 20 heads' float32 scores at 1,024 tokens are 80 MiB,
        # under ``PREFILL_SCORE_BYTES``: one product, kept in fast memory,
        # and no prefill kernel in the program; at 2,048 they are 320 MiB
        # and the dense layer's stack and the routed layers' each call it
        assert len(re.findall(r"prefill_attention[.\d]* = ", hlo)) == (
            2 if program == "prefill_2048" else 0)


def _granite_shows(hlo, program, B, S):
    """granite-4.0-h-micro whole (40 layers, every width as published,
    bf16) at the benchmark's engine sizes. The two kinds of layer run as
    scans that index the WHOLE parameter stacks (no copy of a run's slice
    of one); the slots' 3.6 GB of float32 state and the K/V rows are
    updated in place (ISSUE 35: no copy of a state leaf or of a layer's
    slice of it, and none of the K/V cache, which the chip would lay out
    rows-minor were its 64-wide heads an axis: the heads lie flat, 512
    wide, in the four attention layers' rows); the four attention layers'
    rows are read by the decode attention's kernel; and the program leaves
    room on a 16 GB chip."""
    if program.startswith("decode_block"):
        assert "raytpu.ssm.update" in hlo
        # one attention layer a period
        assert len(_calls(hlo, "decode_attention")) == 1
        # ISSUE 36: every state moves once each way. The two runs of
        # state-space layers in the period of ten each step their states
        # with ``ops/ssm.ssm_update`` on the whole leaf, under the scope
        # ``readers/scope_time.py`` looks for, and nothing else reads a
        # layer's states: a fused computation that read them would have
        # the leaf, a layer of it or the kernel's view of either as a
        # parameter, and the only such parameter is the program's own
        # argument, so no second reader forms ``y``
        kernels = _calls(hlo, "ssm_update")
        assert len(kernels) == 2
        assert all("raytpu.ssm.update" in line for line in kernels)
        assert all(f"f32[36,{B},1,32,128,128]" in line for line in kernels)
        # ISSUE 46: each is handed the live lanes' tiles (the order and,
        # as a grid bound, their count) and still writes into the leaf
        assert all(f"s32[{B}]" in line for line in kernels)
        assert all("output_to_operand_aliasing" in line for line in kernels)
        readers = re.findall(
            rf"(%\S+) = f32\[(?:36,|1,)?{B},(?:64,64|1,32,128),128\]\S* "
            r"parameter\(", hlo)
        assert len(readers) == 1 and readers[0].startswith("%cache"), readers
    else:
        assert "raytpu.ssm.scan" in hlo


def _nemotron_shows(hlo, program, B, S):
    """Nemotron-3-Super's first pipeline stage as one of four chips holds
    it (11 one-branch layers, 128 of 512 experts a routed layer, a quarter
    of the vocabulary, every width as published, bf16) at the benchmark's
    engine sizes: 11.2 GB static (weights 9.30 + state 1.36 + rows 0.54),
    and every bucket's admission beside it under 16 GB. The three kinds of
    layer run as scans that index the WHOLE parameter stacks; the slots'
    1.3 GB of float32 state and the K/V rows (two KV heads of 128, flat in
    a row of the one attention layer) are updated in place; ``ssm_update``
    steps the live lanes' states of 8 B/C groups inside the leaf; the held
    experts are read where they lie by the grouped products, which the
    latent's two projections stand round. No copy of the state, the tails,
    the rows, a stack of experts, of state-space or shared-expert weights,
    the head or the embedding (the one attention layer's 32 MB of queries'
    weights are asked for in another layout, which ``lay_out_for_decode``
    gives them at set-up)."""
    # two grouped products a routed layer's body, in the share's loop
    products = _calls(hlo, "grouped_matmul")
    assert len(products) >= 2 and all(
        "bf16[5,128,1024,2688]" in k or "bf16[5,128,2688,1024]" in k
        for k in products)
    if program.startswith("decode_block"):
        assert "raytpu.ssm.update" in hlo
        assert len(_calls(hlo, "decode_attention")) == 1
        steps = _calls(hlo, "ssm_update")
        # G = 8: a tile is (slots, group, row blocks); each call is handed
        # the live lanes' tiles and writes into the leaf it reads
        assert steps and all(
            f"f32[5,{B},8,8,128,128]" in k and "raytpu.ssm.update" in k
            and "output_to_operand_aliasing" in k for k in steps)
    else:
        assert "raytpu.ssm.scan" in hlo


def _mimo_shows(hlo, program, B, S):
    """MiMo-V2-Flash's first pipeline stage (layers 0-6, ``F(dense) | W W
    W W F W``, 16 of 256 experts, 1/8 vocabulary, every width as
    published, bf16) at the benchmark's engine sizes. ISSUE 39: the two
    full layers' rows (ROW leaves, the KV heads flat, values narrower than
    keys) and the five window layers' rings (STATE leaves) are updated in
    place, no cache leaf, ring or parameter stack is copied (a run of like
    layers is a scan that indexes the WHOLE stacks), both kinds' decode
    attention is the one kernel, a window layer's over its ring, and a
    prefill holds no [S, S] array: the 14,336 bucket, because the dense
    FFN's ``d_ff`` is 16,384 and the 64 query heads' 192-wide keys lie
    12,288 wide in a row."""
    attends = _calls(hlo, "decode_attention")
    if program.startswith("decode_block"):
        # the dense layer and the period's full layer; the run of four
        # window layers (one scan) and the period's last
        assert sum("raytpu.attn.attend" in a for a in attends) == 2
        assert sum("raytpu.swa.attend" in a for a in attends) == 2
        assert len(attends) == 4
    else:
        bucket = program.rsplit("_", 1)[1]
        assert not attends
        assert f"{bucket},{bucket}" not in hlo  # no [S, S] array of any type


def _kimi_shows(hlo, program, B, S):
    """Kimi-Linear's first eight layers (K dense, K K F, K K K F; every
    width as published, 64 of 256 experts and a shared one, 1/4 of the
    vocabulary, bf16) at the benchmark's engine sizes. The runs of like
    layers are scans that index the WHOLE parameter stacks; no layer's
    experts, no state leaf and no layer's slice of one is copied (ISSUE
    44); the slots' 1.2 GB of float32 matrix states, the convolutions'
    tails and the latent rows are updated in place; a decode step steps
    the states with ``ops/kda.kda_update`` on the whole leaf, once a run
    of the period, and reads the two full layers' rows with the decode
    attention's kernel; the admission is the engine's fused form at the
    largest bucket and leaves room on a 16 GB chip."""
    calls = _calls(hlo)
    if program.startswith("decode_block"):
        # one body a run of the period: K(dense) | K K, F, K K K, F
        updates = [line for line in calls if "kda_update" in line]
        assert len(updates) == 3
        assert all("raytpu.kda.update" in line for line in updates)
        assert all(f"f32[6,{B},32,128,128]" in line for line in updates)
        # ISSUE 46: the live lanes' tiles alone, still in place
        assert all(f"s32[{B}]" in line for line in updates)
        assert all("output_to_operand_aliasing" in line for line in updates)
        assert sum("decode_attention" in line for line in calls) == 2
        assert sum("raytpu.moe.experts" in line for line in calls) == 8
    else:
        # ISSUE 52: the chunked delta rule is ONE kernel a run of the
        # period under its scope, fed the prompt's live chunks as a
        # prefetched scalar, and nothing walks the chunks outside it
        bucket = program.rsplit("_", 1)[1]
        chunks = [line for line in calls if "kda_chunk" in line]
        assert len(chunks) == 3
        assert all("raytpu.kda.chunk" in line for line in chunks)
        assert all("s32[1]" in line for line in chunks)
        assert not [line for line in hlo.splitlines()
                    if "raytpu.kda.chunk" in line and " while(" in line]
        assert not any("kda_update" in line for line in calls)
        assert f"[{bucket},{bucket}]" not in hlo  # no prompt's scores whole


def _phi4flash_shows(hlo, program, B, S):
    """Phi-4-mini-flash-reasoning WHOLE (32 layers, the whole vocabulary,
    bf16) at the benchmark's engine sizes. The list of layers has no
    period: three segments, ``(mamba window) x 8`` and ``(gmu cross) x 7``
    as scans and ``mamba attention`` inline, so a program holds six layer
    bodies, not 32 (ISSUE 49); the ONE full layer's rows, the eight rings
    and the nine [16, 5120] float32 states are updated in place; a decode
    step reads the rings, the full layer's rows and, from the cross
    layers, THE SAME rows with the decode attention's kernel; the
    admission is the engine's fused form at the largest bucket, runs the
    Mamba-1 recurrence as ``mamba_scan``, never makes a prompt's scores
    whole and leaves room on a 16 GB chip."""
    calls = _calls(hlo)
    if program.startswith("decode_block"):
        # one body a run of a segment's period: the rings', the full
        # layer's and the cross layers' attentions
        assert len(calls) == 3
        assert all("decode_attention" in line for line in calls)
        assert sum("raytpu.swa.attend" in line for line in calls) == 1
        assert sum("raytpu.attn.attend" in line for line in calls) == 1
        assert sum("raytpu.cross.attend" in line for line in calls) == 1
        assert "raytpu.gmu.gate" in hlo and "raytpu.mamba1.update" in hlo
    else:
        bucket = program.rsplit("_", 1)[1]
        scans = [line for line in calls if "mamba_scan" in line]
        assert len(scans) == 2  # (M W) x 8 and M F
        assert all("raytpu.mamba1.scan" in line for line in scans)
        # ISSUE 53: the ONE full layer's attention over the prompt is the
        # prefill kernel (ten pairs of KV heads, four heads a pair), told
        # the prompt's length; no other kernel is in the program
        attends = [line for line in calls if "prefill_attention" in line]
        assert len(attends) == 1 and len(calls) == 3
        assert "raytpu.attn.attend" in attends[0] and "s32[1]" in attends[0]
        assert "raytpu.upper.last_token" in hlo
        assert f"[{bucket},{bucket}]" not in hlo  # no prompt's scores whole


def _evabyte_shows(hlo, program, B, S):
    """One pipeline stage of EvaByte (8 of 32 layers, every width as
    published, the whole byte vocabulary and all eight heads, bf16) at the
    benchmark's engine sizes: slots of 32,768 positions, 3,968 rows a
    slot-layer (8.3 GB). A model with no "attn" layer: the ONE pair of row
    leaves is the "eva" layers', updated in place by the token's write, by
    the loop that folds a closed window and by an admission; a decode step
    reads a slot's summaries and open window with the decode attention's
    kernel (32 heads of 128) and copies no layer of the stacked weights
    (the q, k, v projections' stacks [8, 4096, 32, 128] are asked for in
    another layout by the decode step, as GPT-J's: the engine places them
    so once, ``generation.lay_out_for_decode``); the admission at the
    largest bucket never makes a prompt's scores whole, attends through a
    kernel that reads q, k, v where they lie (ISSUE 65) and leaves room on
    a 16 GB chip (ISSUE 55)."""
    calls = _calls(hlo)
    if program.startswith("decode_block"):
        # eight layers of one kind: one body, one kernel
        assert len(calls) == 1 and "decode_attention" in calls[0]
        assert "raytpu.eva.attend" in calls[0]
    else:
        # ISSUE 65: the windows' queries over the summaries before them
        # and their own rows, in the kernel: one body, one call
        bucket = program.rsplit("_", 1)[1]
        assert len(calls) == 1 and "eva_attention" in calls[0]
        assert "raytpu.eva.attend" in calls[0]
        # q and k straight from the rotation, v from its projection, the
        # output into its own: the call is handed no copy of the bucket
        # (a kernel over [S, H x D] rows was handed three and gave one)
        handed = calls[0].split("custom-call(")[1].split(")")[0]
        assert "copy" not in handed, handed
        assert not _copies(hlo, "bf16[1,32,")
        assert f"[{bucket},{bucket}]" not in hlo  # no prompt's scores whole


class Fits(NamedTuple):
    """What one served configuration's compiled programs are held to. A
    shape is spelt with ``{B}``, the file's ``max_slots``, and ``{S}``, the
    rows a slot holds of a layer (the file's ``max_len``; EvaByte's windows
    and summaries), which the test fills in from what it read."""
    programs: Tuple[str, ...]  # ``Served.lower``'s names
    peak_gib: float  # arguments + temporaries + outputs - aliased, under
    no_copy_of: Tuple[str, ...]  # ``_copies`` finds none of these
    scopes: Tuple[str, ...]  # the compiled text names each
    shows: Callable  # (text, program, B, S): what only this model shows
    foot: Optional[Tuple[int, int]] = None  # a slot's (state, row) bytes
    flat_kv: Optional[Tuple[int, int]] = None  # "k": (layers, a row's width)
    static: Tuple[float, float] = (0, float("inf"))  # the arguments' bytes


FITS = {
    "glm47": Fits(
        ("decode_block_8", "prefill_1024", "prefill_2048"), 14.5,
        no_copy_of=("bf16[7,64,",),  # the experts stay in the stack
        scopes=("raytpu.mla.attend",), shows=_glm47_shows,
        foot=(0, 8 * 576 * 2)),
    "granite": Fits(
        ("decode_block_2", "prefill_2048"), 14.5,
        no_copy_of=(
            "f32[36,{B},64,", "f32[{B},64,64,128", "f32[1,{B},64,",
            "bf16[4,{B},{S},", "bf16[36,{B},13056",
            "bf16[36,2048,4", "bf16[36,2048,8192", "bf16[36,8192,",
            "bf16[100352,"),
        scopes=("raytpu.ssm.project", "raytpu.ssm.conv", "raytpu.ssm.gate"),
        shows=_granite_shows, flat_kv=(4, 512)),
    "nemotron": Fits(
        ("decode_block_2", "prefill_256", "prefill_512", "prefill_1024",
         "prefill_2048", "prefill_4096", "prefill_6144"), 13.5,
        no_copy_of=(
            "f32[5,{B},128,", "f32[5,{B},8,", "f32[{B},128,64,128",
            "bf16[5,{B},30720", "bf16[1,{B},{S},", "bf16[5,128,",
            "bf16[128,1024,", "bf16[128,2688,", "bf16[5,4096,",
            "bf16[5,8192,", "bf16[5,5376,", "bf16[5,1024,",
            "bf16[4096,32768", "bf16[32768,"),
        scopes=("raytpu.ssm.project", "raytpu.ssm.conv", "raytpu.ssm.gate",
                "raytpu.moe.route", "raytpu.moe.experts",
                "raytpu.moe.latent", "raytpu.moe.shared",
                "raytpu.attn.attend"),
        shows=_nemotron_shows, flat_kv=(1, 256), static=(11.1e9, 11.3e9)),
    "mimo": Fits(
        ("decode_block_2", "prefill_14336"), 14.5,
        no_copy_of=(
            "bf16[2,{B},{S},", "bf16[1,{B},{S},",  # the rows
            "bf16[5,{B},128,", "bf16[1,{B},128,",  # the rings
            "bf16[5,16,", "bf16[5,4096,", "bf16[5,64,",  # window stack
            "bf16[1,16,", "bf16[1,4096,64,192", "bf16[1,4096,4,",
            "bf16[1,64,128,4096", "bf16[1,4096,256",  # the other stacks
            "bf16[16,4096,2048", "bf16[16,2048,4096",  # a layer's experts
            "bf16[19072,", "bf16[4096,19072"),
        scopes=("raytpu.swa.project", "raytpu.swa.attend",
                "raytpu.swa.ring", "raytpu.attn.project",
                "raytpu.attn.attend", "raytpu.moe.experts"),
        shows=_mimo_shows, foot=(3_276_800, 5120)),
    "kimi": Fits(
        ("decode_block_2", "admission_8192"), 13.0,
        no_copy_of=(
            "f32[6,{B},32,128,", "f32[{B},32,128,128", "f32[1,{B},32,128,",
            "bf16[6,{B},36864", "bf16[2,{B},{S},", "bf16[{B},{S},",
            "bf16[5,64,", "bf16[2,64,", "bf16[64,2304,", "bf16[64,1024,",
            "bf16[5,2304,12288", "bf16[40960,", "bf16[2304,40960"),
        scopes=("raytpu.kda.project", "raytpu.kda.conv", "raytpu.kda.gate",
                "raytpu.mla.project", "raytpu.mla.attend",
                "raytpu.moe.route", "raytpu.moe.experts",
                "raytpu.moe.shared"),
        shows=_kimi_shows, foot=(13_025_280, 2304)),
    "phi4flash": Fits(
        ("decode_block_8", "admission_16384"), 14.0,
        no_copy_of=(
            "f32[9,{B},16,5120", "bf16[9,{B},15360", "bf16[8,{B},512,",
            "bf16[1,{B},{S},", "bf16[{B},{S},", "bf16[9,2560,",
            "bf16[7,2560,", "bf16[200064,", "bf16[2560,200064"),
        scopes=("raytpu.mamba1.project", "raytpu.mamba1.conv",
                "raytpu.mamba1.gate", "raytpu.swa.project",
                "raytpu.attn.project", "raytpu.diff.combine"),
        shows=_phi4flash_shows, foot=(24_197_120, 5120)),
    "evabyte": Fits(
        ("decode_block_8", "admission_28672"), 14.0,
        no_copy_of=(
            "bf16[8,{B},{S},", "bf16[{B},{S},", "bf16[8,4096,11008",
            "bf16[4096,11008", "bf16[8,11008,", "bf16[11008,4096",
            "bf16[8,32,128,4096"),
        scopes=("raytpu.eva.project", "raytpu.eva.attend",
                "raytpu.eva.pool"),
        shows=_evabyte_shows, foot=(0, 8 * 16384)),
}
_FITS = [(name, program) for name, row in FITS.items()
         for program in row.programs]


@pytest.mark.parametrize(
    "name, program", _FITS, ids=[f"{n}-{p}" for n, p in _FITS])
def test_serving_programs_fit_one_v5e(v5e, as_on_the_chip, name, program):
    """A served configuration's programs (a row of ``FITS``: the name is
    ``tools/lowered_texts.SERVED``'s, the model and the engine's sizes are
    the benchmark's file's) compile for one v5e: the whole cache is
    updated in place, the program leaves room on a 16 GB chip, no array the
    row lists is copied, every scope a reader of the trace looks for is
    there, and the text shows what the row's ``shows`` says and why."""
    from ray_tpu.models import generation as gen

    row, s = FITS[name], served(name, v5e)
    slots = s.engine["max_slots"]
    (rows,) = {a.shape[2] for a in jax.tree.leaves(gen.cache_rows(s.cache))}
    if row.flat_kv:  # the heads lie flat
        layers, width = row.flat_kv
        assert s.cache["k"].shape == (
            layers, slots, s.engine["max_len"], width)
    low = s.lower(program)
    if program.startswith("admission"):
        assert list(low.out_info[3]) == list(gen.prefill_stat_keys(s.cfg))
    compiled = low.compile()
    mem = compiled.memory_analysis()
    foot = gen.slot_footprint(s.cache)
    if row.foot:
        assert (foot["state_bytes"], foot["row_bytes"]) == row.foot
    cache_bytes = slots * (foot["state_bytes"] + rows * foot["row_bytes"])
    static = mem.argument_size_in_bytes
    assert row.static[0] < static < row.static[1]
    assert mem.alias_size_in_bytes >= cache_bytes  # updated in place
    peak = (static + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes)
    assert peak < row.peak_gib * 2 ** 30
    hlo = compiled.as_text()
    for of in row.no_copy_of:
        assert not _copies(hlo, of.format(B=slots, S=rows)), of
    for scope in row.scopes:
        assert scope in hlo, scope
    row.shows(hlo, program, slots, rows)


def test_glm52_admission_attends_its_chosen_rows_through_the_kernel(
        v5e, as_on_the_chip):
    """GLM-5.2's first pipeline stage as ``serve-glm52-longdoc-steady``
    serves it (6 layers, 16 of 256 experts, the file's slots and rows),
    the admission of the 12,288 bucket. ISSUE 56: a group of 16 heads
    attends under the choice's mask through ``blocked_causal_attention``
    (Mosaic has to take 1,024 x 1,024 blocks of an int8 mask beside
    256-wide keys and values), once in the dense layer's stack and once in
    the routed layers', under the scope ``model.prefill_dsa_time_share``
    reads; the mask [S, S] is made in int8 and handed over where it lies;
    no float32 score tile exists outside the kernel; and the program is no
    larger than the tile loop's was (13.84 GiB by this count)."""
    from ray_tpu.models import generation as gen

    s, bucket = served("glm52", v5e), 12288
    low = s.lower(f"admission_{bucket}")
    assert list(low.out_info[3]) == list(gen.prefill_stat_keys(s.cfg))
    compiled = low.compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 13.84 * 2 ** 30
    hlo = compiled.as_text()
    attends = _calls(hlo, "prefill_attention")
    assert len(attends) == 2
    assert all("raytpu.mla.attend" in line for line in attends)
    assert all("s32[1]" in line and f"s8[1,{bucket},{bucket}]" in line
               for line in attends)
    assert not _copies(hlo, f"s8[{bucket},") and not _copies(hlo, "s8[1,")
    assert f"pred[{bucket},{bucket}]" not in hlo  # the choice is int8
    assert "f32[16,1024,1024]" not in hlo


@pytest.mark.parametrize("kernel", ["ssm_update", "kda_update"])
def test_a_state_kernel_told_the_live_lanes_compiles_in_place(
        v5e, kernel, monkeypatch):
    """ISSUE 46: ``ops/ssm.ssm_update`` at granite's shapes (48 slots of
    64 heads x 64 x 128, 36 layers) and ``ops/kda.kda_update`` at Kimi's
    (96 slots of 32 heads x 128 x 128, 6 layers), handed ``live``: Mosaic
    takes the grid whose slot axis is as long as the live tiles are many
    (a bound read on the device) with the tiles' order prefetched; it is
    one custom call, the donated leaf is its output, and no copy of the
    leaf appears in the compiled text."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from ray_tpu.ops.kda import kda_update
    from ray_tpu.ops.ssm import ssm_update

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    bf16 = jnp.bfloat16
    if kernel == "ssm_update":
        b, leaf = 48, (36, 48, 64, 64, 128)
        args = (arr(leaf), arr((), jnp.int32), arr((b, 64, 64), bf16),
                arr((b, 64)), arr((64,)), arr((b, 1, 128), bf16),
                arr((b, 1, 128), bf16), arr((64,)), arr((b,), jnp.bool_))
        fn = ssm_update
    else:
        b, leaf = 96, (6, 96, 32, 128, 128)
        args = (arr(leaf), arr((), jnp.int32), arr((b, 32, 128), bf16),
                arr((b, 32, 128), bf16), arr((b, 32, 128), bf16),
                arr((b, 32, 128)), arr((b, 32)), arr((b,), jnp.bool_))
        fn = kda_update
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    size = 4
    for n in leaf:
        size *= n
    assert compiled.memory_analysis().alias_size_in_bytes >= size
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and kernel in calls[0]
    assert f"s32[{b}]" in calls[0]  # the live tiles' order
    assert "output_to_operand_aliasing" in calls[0]
    assert not _copies(hlo, "f32[%d,%d," % leaf[:2])



@pytest.fixture(scope="module")
def gptj_served(v5e, as_on_the_chip):
    """GPT-J-6B int8 as the benchmark's file sizes its engine, as shapes
    on the described chip, twice: the weights in the layouts the chip
    hands out, and in the layouts the engine leaves them in
    (``generation.lay_out_for_decode``: what the compiled burst-length
    ``decode_block`` asks for); and what each block length asks for."""
    from ray_tpu.models import generation as gen

    made = served("gptj", v5e)
    asked = [gen.decode_weight_formats(
        made.params, made.cfg, made.engine["max_slots"],
        made.engine["max_len"], steps) for steps in made.block_steps]
    held = made._replace(params=gen.told_where_they_lie(jax.tree.map(
        lambda a, f: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=f),
        made.params, asked[0]), asked[0]))
    return made, held, asked


def test_gptj_decode_asks_for_three_weights_in_another_layout(gptj_served):
    """Both block lengths ask for the same layouts, and they differ from
    what the chip hands out for exactly the three stacked int8 q/k/v
    projections (the head dimension outside the contracted one): 3 leaves,
    1.41e9 bytes, what ``stats()["weights_relaid"]`` reads on the chip."""
    made, _held, asked = gptj_served
    assert jax.tree.leaves(asked[0]) == jax.tree.leaves(asked[1])
    handed = made.lower(f"decode_block_{made.block_steps[0]}").compile(
    ).input_formats[0][0]
    moved = [(tuple(k.key for k in path[:3]), a.shape, a.dtype,
              f.layout.major_to_minor)
             for (path, a), f, h in zip(
                 jax.tree_util.tree_flatten_with_path(made.params)[0],
                 jax.tree.leaves(asked[0]), jax.tree.leaves(handed))
             if f.layout != h.layout]
    assert sorted(moved) == [
        (("layers", "attn", w), (28, 4096, 16, 256), jnp.int8, (0, 2, 1, 3))
        for w in ("wk", "wq", "wv")]


@pytest.mark.parametrize(
    "program", ["decode_block_2", "decode_block_8", "prefill_128",
                "prefill_1024", "prefill_256", "prefill_512"])
def test_gptj_serving_programs_copy_no_stacked_weight(gptj_served, program):
    """Lowered the way the engine calls them since ISSUE 29 (the weights
    carry the layouts ``decode_block`` asked for), ``decode_block`` holds
    no ``copy`` of a stacked ``[28, ...]`` int8 weight and under 0.1 GiB
    of temporaries (with the layouts the chip hands out: three copies of
    470 MB a block, 1.32 GiB), and ``prefill_into_slot`` no int8 copy at
    any bucket (before: 3 a layer through HBM). ISSUE 62: from 256 rows up
    the compiler wanted ``wq`` / ``wk`` contracted-axis minor and, handed
    the decode's layout, sliced each layer's 16 MB out of the stack and
    laid them out again (two copies a layer, 3.9-4.2 ms a program on the
    chip, which a test of this name had allowed at 1,024); the admission
    now holds each int8 leaf to the order it lies in
    (``generation._read_where_they_lie``), and no operation of it has a
    layer's int8 projection for its result."""
    made, held, _asked = gptj_served
    now = held.lower(program).compile()
    hlo = now.as_text()
    if program.startswith("decode"):
        assert not _copies(hlo, "s8[28,")
        assert now.memory_analysis().temp_size_in_bytes < 0.1 * 2 ** 30
        assert now.memory_analysis().alias_size_in_bytes >= sum(
            a.size * a.dtype.itemsize
            for a in jax.tree.leaves(held.cache))  # the cache, in place
        # ISSUE 33: K and V are read by the decode attention's kernel,
        # where they lie in the stacked cache
        kernels = _calls(hlo)
        assert len(kernels) == 1 and "decode_attention" in kernels[0]
        assert not _copies(hlo, "bf16[28,{max_slots},{max_len},".format(
            **held.engine))
    else:
        assert not _copies(hlo, "s8[")
        # nor a layer's slice written out for the product to read: every
        # int8 array an operation of the program yields is a view
        # (dynamic-slice, bitcast) inside the fusion that multiplies
        assert not re.findall(
            r"= s8\[1,4096,16,256\]\S* (?:fusion|copy)\(", hlo)
    before = made.lower(program).compile().as_text()
    assert len(_copies(before, "s8[")) == 3  # what the layouts took away


@pytest.mark.parametrize("bucket", [2560, 8192, 28672])
def test_eva_admission_attends_through_its_kernel(
        v5e, as_on_the_chip, bucket):
    """ISSUE 65, from the lowered text alone (nothing is compiled):
    EvaByte's admission holds ONE kernel (eight layers, one body), called
    under ``raytpu.eva.attend`` with a head's queries and keys as
    [128, the bucket's whole windows] (2,560: a window and a quarter,
    padded to two), its values and its output [the windows, 128], and the
    whole windows' summaries alike (in blocks of 1,024 where they are
    more), and no float32 scores of a block of 256 queries against a
    window or the bucket's summaries (the parent's ``lax.map``:
    ``[1, 32, 256, 2048]`` and ``[1, 32, 256, NS]``)."""
    text = served("evabyte", v5e).lower(f"admission_{bucket}").as_text(
        debug_info=True)
    tokens = -(-bucket // 2048) * 2048
    summaries = bucket // 2048 * 128
    summaries = -(-summaries // min(summaries, 1024)) * min(summaries, 1024)
    kernels = [line for line in text.splitlines()
               if "stablehlo.custom_call @tpu_custom_call" in line]
    assert len(kernels) == 1

    def columns(n):
        return f"tensor<1x32x128x{n}xbf16>"

    def rows(n):
        return f"tensor<1x32x{n}x128xbf16>"

    assert kernels[0].rstrip().rsplit(" : ", 1)[1].startswith(
        f"(tensor<1xi32>, {columns(tokens)}, {columns(tokens)}, "
        f"{rows(tokens)}, {columns(summaries)}, {rows(summaries)}) -> "
        f"{rows(tokens)}")
    site = re.search(r"call @eva_attention\(.*loc\((#loc\d+)\)",
                     text).group(1)
    assert f'{site} = loc("raytpu.eva.attend/jit(eva_attention)' in text
    assert not re.findall(r"tensor<[\dx]*32x256x\d+xf32>", text)
    assert "stablehlo.while" in text  # the scan over the eight layers stays


def _admission_forms(s, bucket):
    """``prefill_into_slot`` compiled both ways at one bucket: the plain
    form, and the engine's admission (with the five lanes, the request's
    temperature and its seed)."""
    return (s.lower(f"prefill_{bucket}").compile(),
            s.lower(f"admission_{bucket}").compile())


def _check_admission(plain, fused, s, cache_leaves_of, tail_slots=0):
    """ISSUE 38: the admission is the prefill with a tail on its logits.
    The whole cache and the five lanes are updated in place (each an
    argument aliased to an output), no cache leaf (``cache_leaves_of``,
    spelt with ``{B}`` and ``{S}`` as ``FITS`` spells them) is copied, and
    the program needs no more room than the plain form but for the logits
    it now keeps to itself and the lanes. ``tail_slots``: how many of the
    tail's sixteen 512-byte buffers (the sampled token's key arithmetic,
    the lanes' updates and their copies' flags), which the heap places
    16 KiB apart, lie ABOVE the program's largest temporaries and not in
    a hole between them (the compiler's buffer assignment says which:
    dump it with ``compiler_options={"xla_dump_to": ...}``)."""
    slots, rows = s.engine["max_slots"], s.engine["max_len"]
    hlo = fused.as_text()
    leaves = jax.tree.leaves(s.cache)
    aliased = re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)",
                         hlo.split("\n", 1)[0])
    assert len(aliased) == len(leaves) + 5, hlo.split("\n", 1)[0][-600:]
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    lane_bytes = 5 * slots * 4
    mem, was = fused.memory_analysis(), plain.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes + lane_bytes
    assert was.alias_size_in_bytes >= cache_bytes
    for of in cache_leaves_of:
        assert not _copies(hlo, of.format(B=slots, S=rows)), of
    logits_bytes = s.cfg.vocab_size * 4
    room = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    room_was = was.argument_size_in_bytes + was.temp_size_in_bytes
    assert room <= (room_was + logits_bytes + lane_bytes + 4096
                    + tail_slots * 16384), (room, room_was)
    # one token leaves the program where the logits did
    assert mem.output_size_in_bytes <= (
        was.output_size_in_bytes + lane_bytes + 4096)


def test_gptj_admission_is_the_prefill_in_place(gptj_served):
    """The GPT-J cells' admission at the documents' bucket (the file's
    largest: the bucket is the slot, and the program's copy of it is as
    long as it was). Since the layers attend the prompt's own keys and
    values and no longer read the slot's rows back (ISSUE 59; 322 MB less
    of temporaries, 938.8 -> 616.8 MB), eleven of the tail's small buffers
    (the lanes' copies, the sampled token's arithmetic) top the heap where
    five did: the heap's 544,211,456 B against the plain form's
    544,031,232, 11 x 16 KiB."""
    _made, held, _asked = gptj_served
    bucket = held.engine["prefill_buckets"][-1]
    assert bucket == held.engine["max_len"]
    plain, fused = _admission_forms(held, bucket)
    _check_admission(plain, fused, held, ("bf16[28,{B},{S},",),
                     tail_slots=11)


@pytest.mark.parametrize("bucket", [128, 1024])
def test_gptj_admission_works_on_its_bucket_in_fast_memory(
        gptj_served, bucket):
    """ISSUE 59: GPT-J's admission as the cells serve it (28 layers, int8,
    the file's slots of 1,024 rows) works on the bucket's rows of its slot
    and on no other. Its own copy of the slot is bucket-long (a 128-bucket
    program holds no ``[28,1,1024,..]`` array: the parent zeroed, carried
    and wrote back 470 MB whatever the bucket, and its temporaries were
    939.8 MB at 128 and 938.8 MB at 1,024, the slot's rows twice; now 0.6
    MB and 616.8 MB), the bucket's queries are scored against the bucket's
    own rows in one product (16 heads x 1,024 x 1,024 float32 scores are
    64 MiB, under ``PREFILL_SCORE_BYTES``) whose scores the compiler keeps
    in fast memory (memory space 1 in the compiled text: they never cross
    HBM, which is why the prefill kernel, at the same 0.11 ms a layer, only
    adds the copies that lay its operands out), and the cache stays donated
    and updated in place."""
    from ray_tpu.ops.attention import prefill_by_kernel

    _made, held, _asked = gptj_served
    slots = held.engine["max_slots"]
    assert not prefill_by_kernel(held.cfg.n_heads, bucket)
    _plain, fused = _admission_forms(held, bucket)
    hlo = fused.as_text()
    assert "tpu_custom_call" not in hlo  # no prefill kernel
    scores = re.findall(r"f32\[16,(\d+),(\d+)\]\{([^}]*)\}", hlo)
    assert scores and {(int(a), int(b)) for a, b, _ in scores} == {
        (bucket, bucket)}
    # where a fusion hands them to the next (inside a fusion's body a
    # shape carries no memory space)
    assert any("S(1)" in layout for _a, _b, layout in scores)
    # the program's copy of the slot: the bucket's rows of all 28 layers
    assert {int(n) for n in re.findall(
        r"bf16\[28,1,(\d+),16,256\]", hlo)} == {bucket}
    mem = fused.memory_analysis()
    assert mem.temp_size_in_bytes < {128: 8, 1024: 640}[bucket] * 2 ** 20
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(held.cache))
    assert mem.alias_size_in_bytes >= cache_bytes + 5 * slots * 4
    assert not _copies(hlo, "bf16[28,{max_slots},{max_len},".format(
        **held.engine))
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 12 * 2 ** 30


def test_ssm_hybrid_admission_is_the_prefill_in_place(v5e, as_on_the_chip):
    """granite-4.0-h-micro's admission at the cell's sizes, at its
    largest bucket: the state leaves and the K/V rows alike."""
    s = served("granite", v5e)
    plain, fused = _admission_forms(s, s.engine["prefill_buckets"][-1])
    _check_admission(
        plain, fused, s,
        ("f32[36,{B},64,", "f32[{B},64,64,128", "f32[1,{B},64,",
         "bf16[4,{B},{S},", "bf16[36,{B},13056"))


def test_mimo_admission_moves_no_array_of_all_the_sorted_pairs(
        v5e, as_on_the_chip):
    """ISSUE 43: MiMo-V2-Flash's admission program of the 5,120 bucket (the
    mean prompt's) at the benchmark's engine sizes. Its six routed layers
    hold 16 of 256 experts, so 15 of 16 of the 5,120 x 8 sorted (token,
    expert) pairs go to experts another chip holds: ``routed_ffn`` walks
    the live pairs' tiles in a loop, and the program holds no
    ``[40960, 4096]`` array at all (the parent's held 66 lines of them: the
    gather, the two kernels' rows, the select, the un-sort, the sum); it
    needs less memory than the parent's 11.378 GiB, and the routed
    layers' two counters leave with the first token."""
    from ray_tpu.models import generation as gen

    s = served("mimo", v5e)
    low = s.lower("admission_5120")
    assert list(low.out_info[3]) == list(gen.prefill_stat_keys(s.cfg))
    compiled = low.compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 11.378 * 2 ** 30
    hlo = compiled.as_text()
    assert "[40960,4096]" not in hlo and "[40960,2048]" not in hlo
    # every grouped product sits in the loop's body (two a run of like
    # layers: W W W W, F, W), beside the scatter back to token order
    kernels = _calls(hlo, "raytpu.moe.experts")
    assert len(kernels) == 6
    assert all("raytpu.moe.experts/while/body" in line for line in kernels)
    assert any(" scatter(" in line and "raytpu.moe.experts/while/body" in line
               for line in hlo.splitlines())
    # ISSUE 53: each full layer's causal attention over the prompt is ONE
    # kernel under its scope (the dense layer's and the period's), fed the
    # prompt's length as a prefetched scalar, and nothing walks tiles
    # outside it
    attends = _calls(hlo, "prefill_attention")
    assert len(attends) == 2
    assert all("raytpu.attn.attend" in line for line in attends)
    assert all("s32[1]" in line for line in attends)
    assert not [line for line in hlo.splitlines()
                if "raytpu.attn.attend" in line and " while(" in line]


# sha256 of ``lower(...).as_text()`` on the CPU (where a kernel is its
# interpreter's jaxpr: the text carries no source line) of GLM-4.7-Flash's
# decode programs at the benchmark's engine sizes. Until PR 44 they were
# what the parent of ISSUE 43 (727df70) lowers; PR 44 changed one thing in
# them: a parked lane's latent row is written past the last row and
# dropped (``_decode_attn``), a select over the lanes' positions a layer
GLM47_DECODE_TEXTS = {
    "decode_block_2": (
        "c611aa7f2907d1f6a87196172b779c64"
        "e98f2e9fb5025e3e00ae333e4f9b9f2b"),
    "decode_block_8": (
        "a12604c86c8ccee7ac1d379a0d308953"
        "5d9fbaa56208e052d721c20a615eb1e5"),
    "decode_step_multi": (
        "f08946530c248b5eae65707c46f66381"
        "e5aa617340d416690e8ff62f5317373b"),
}


@pytest.mark.parametrize("program", sorted(GLM47_DECODE_TEXTS))
def test_a_whole_routed_layer_keeps_its_decode_programs(
        program, monkeypatch, request):
    """ISSUE 43 changes what ``routed_ffn`` does for a SHARE of a layer's
    experts. GLM-4.7-Flash holds every expert: every pair is live, and its
    decode programs lower to the text they lowered to before (the counter
    ``routed_ffn`` gained is dropped before it reaches them)."""
    import hashlib

    # whatever ``as_on_the_chip`` has steered for the tests above; and a
    # trace one of them made of this program holds the kernels
    # uninterpreted (as this one's would hold them interpreted)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)
    text = hashlib.sha256(
        served("glm47").lower(program).as_text().encode()).hexdigest()
    assert text == GLM47_DECODE_TEXTS[program]
