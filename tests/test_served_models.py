"""Every served model against its plain reference, one body a claim and
one row of ``MODELS`` a model: the parameter tree against its axes and its
count, the uncached forward, the engine's two programs through a slot
(rows, rings, states), a prefill beside other slots, a reused slot,
``generate``, the ablations a comparison must refuse, the reference's
independence of the program, and the model's benchmark cell resolved and
rehearsed. The reference is the benchmark's own file, the one that decides
a cell's ``correct``: there is no other.
What only one model has (its kernels, scans, selection, shares, published
numbers) is in that model's own file. A new model is a row here.

Every case's id begins with its model's name: under the driver's ``--dist
loadfile`` the cases of ONE model are a work unit (``tests/conftest.py``:
``pytest_xdist_make_scheduler``), so that each model's programs are
compiled by one worker, as when each model had a file."""

import json
import os
import subprocess
import sys
from functools import lru_cache
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import (
    reference_dsa_moe,
    reference_eva,
    reference_kda_moe,
    reference_mla_moe,
    reference_sambay,
    reference_ssm,
    reference_ssm_moe,
    reference_swa_moe,
)
from ray_tpu.models import generation as gen
from ray_tpu.ops import attention
from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    param_logical_axes,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
# what a reference may not name below its header: the program's code
PROGRAM = ("ray_tpu", "generation", "transformer", "ops.")


class Through(NamedTuple):
    """One run through the engine's two programs: ``lanes`` {slot: prompt
    length} of ``slots``, each prompt padded to ``bucket``, in a cache of
    ``s_max`` rows, then ``steps`` decode steps (the other lanes parked)."""
    lanes: Dict[int, int]
    slots: int
    s_max: int
    bucket: int
    steps: int
    scores_at_once: bool = True  # False: PREFILL_SCORE_BYTES 0, the kernel


class Cell(NamedTuple):
    """A model's benchmark cell as ``run.py --list`` shows it, the extra
    arguments of its rehearsal, and what to check of what it printed."""
    name: str
    runner: str
    traffic: str
    end_to_end: Tuple[str, ...]
    per_layer: Tuple[str, ...]
    rehearsed: Callable  # (values, note) -> None
    slower: Optional[float] = None  # requests/s, where the host needs fewer
    seconds: int = 6


class Model(NamedTuple):
    cfg: TransformerConfig  # the tiny preset, float32
    ref: object  # the plain reference: the benchmark's module
    hp: Dict  # what the reference is told of the config
    foreign: Tuple[str, ...]  # what the reference's body may not name
    tol: float  # float32 against float32: rounding order only
    metric: int  # of ``vector_distance``: 0 largest, 1 root mean square
    stacks: Dict[str, set]  # keys of each stack of the parameter tree
    shapes: Dict[str, Tuple[int, ...]]  # "stack/../leaf" -> its shape
    counters: Tuple[str, ...]  # block_stat_keys ends with these
    state: Optional[Tuple[str, float]]  # the recurrent STATE leaf, its bound
    through: Dict[str, Through]
    generated: Tuple[int, int]  # generate(): prompt length, max_len
    refused: Through  # the run the ablations are told apart on
    ablations: Tuple[Dict, ...]
    floor: float  # an ablation lies at least this far off
    ablated_state: int  # which state layer a comparison looks at
    cell: Cell


def _mla_rehearsed(values, note):
    assert values["engine.moe_expert_read_share"] is not None
    assert values["model.moe_load_imbalance"] is not None


def _dsa_rehearsed(values, note):
    share = values["engine.attn_select_share"]
    assert share is not None and 0 < share < 100


def _ssm_rehearsed(values, note):
    # the rehearsal's engine: 4 slots, 4 state layers (the metric's scale
    # is the cell's 36), and the states moved are the live lanes' alone
    # (ISSUE 46): a whole share, 100 x 36 / 4
    share = values["engine.state_live_share"]
    assert share is not None and abs(share - 900) < 1e-6
    end = note["backlog"]["end"]
    assert end["slot_state_bytes"] > 0 and end["slot_row_bytes"] > 0
    assert (end["state_slots_updated"] + end["state_slots_skipped"]
            == 4 * 4 * end["steps"])
    assert end["slot_steps"] * 4 <= end["state_slots_updated"]
    assert 0 < values["engine.state_skip_share"] < 100


def _swa_rehearsed(values, note):
    share = values["engine.window_rows_share"]
    assert share is not None and 0 < share < 100
    end = note["backlog"]["end"]
    assert end["slot_state_bytes"] == 3 * 8 * (4 * 64 + 4 * 32) * 2
    assert 0 < end["window_rows_read"] <= 3 * 8 * end["slot_steps"]
    probe = note["probe"]
    assert probe["replayed"] and probe["window_layer"]["ring_median"] < 0.05


def _kda_rehearsed(values, note):
    # the rehearsal's engine: 4 kda layers (the metric's scale is the
    # cell's 6), and the states moved are the live lanes': 100 x 6 / 4
    share = values["engine.state_live_share.kda"]
    assert share is not None and abs(share - 150) < 1e-6
    assert 0 < values["engine.state_skip_share"] < 100
    end = note["backlog"]["end"]
    assert end["slot_state_bytes"] == 4 * (2 * 16 * 16 * 4 + 3 * 3 * 32 * 2)
    assert end["slot_row_bytes"] == 2 * (16 + 8) * 2
    assert (end["state_slots_updated"] + end["state_slots_skipped"]
            == 4 * end["capacity_steps"])
    assert end["slot_steps"] * 4 <= end["state_slots_updated"]
    assert end["attn_rows_read"] > 0 and end["moe_assignments"] > 0
    probe = note["probe"]
    assert probe["replayed"] and probe["refused_by"] == []
    assert probe["kda_layer"]["step_median"] < 0.05


def _sambay_rehearsed(values, note):
    # the rehearsal's engine: one "attention" layer's rows read by it and
    # by the one "cross" layer above, two rings
    share = values["engine.shared_rows_share"]
    assert share is not None and 0 < share < 100
    end = note["backlog"]["end"]
    # one cross layer walks what the full layer walks (the host's count
    # and the device's differ by the blocks in which a lane is retired)
    assert 0 < abs(end["cross_rows_read"] / end["attn_rows_read"] - 1
                   ) + 1 < 1.1
    assert end["window_rows_read"] > 0
    # three mamba states and tails, two rings of 8 rows
    assert end["slot_state_bytes"] == (
        3 * (16 * 256 * 4 + 3 * 256 * 2) + 2 * 8 * 2 * 128 * 2)
    assert end["slot_row_bytes"] == 2 * 128 * 2
    probe = note["probe"]
    assert probe["replayed"] and probe["refused_by"] == []


def _eva_rehearsed(values, note):
    # the rehearsal's engine: windows of 32 rows in chunks of 4, prompts of
    # 16-128 tokens: both kinds of row are read, and windows close
    share = values["engine.summary_rows_share"]
    assert share is not None and 0 < share < 100
    end = note["backlog"]["end"]
    assert end["eva_windows_closed"] > 0 and end["eva_window_rows_read"] > 0
    # the host's count walks whole chunks of the rows a position leaves
    assert end["attn_rows_read"] > 0
    assert end["slot_state_bytes"] == 0
    assert end["slot_row_bytes"] == 2 * 2 * 128 * 2
    probe = note["probe"]
    assert probe["replayed"] and probe["refused_by"] == []
    assert probe["summary_prefill"] is not None
    assert probe["summary_decode"] is not None


def _ssm_moe_rehearsed(values, note):
    # the rehearsal's engine: 4 slots, 3 state layers, 3 routed layers
    # holding 4 of 16 experts, 3 a token
    # (the scopes' shares need a device trace; the host's has none)
    for name in ("engine.moe_expert_read_share", "model.moe_load_imbalance",
                 "kernel.decode_hbm_share.ssm_moe"):
        assert values[name] is not None, name
    assert 0 < values["engine.state_skip_share"] < 100
    end = note["backlog"]["end"]
    assert end["slot_state_bytes"] == 3 * (8 * 32 * 16 * 4
                                           + 3 * (256 + 2 * 2 * 16) * 2)
    assert end["slot_row_bytes"] == 2 * 2 * 64 * 2
    assert (end["state_slots_updated"] + end["state_slots_skipped"]
            == 3 * end["capacity_steps"])
    assert end["attn_rows_read"] > 0 and end["moe_assignments"] > 0
    assert end["moe_experts_capacity"] == 4 * 3 * end["steps"]
    probe = note["probe"]
    assert probe["replayed"] and probe["decode_rel"] < 0.2


def _latent_hp(cfg):
    return {"n_heads": cfg.n_heads, "qk_nope": cfg.qk_nope_dim,
            "qk_rope": cfg.qk_rope_dim, "kv_rank": cfg.kv_lora_rank,
            "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta}


MLA = TransformerConfig.tiny_mla_moe(dtype=F32)
# six layers, full | shared shared shared full shared; 16 rows a query; the
# stack holds experts 2..5 of 8
DSA = TransformerConfig.tiny_dsa_moe(
    dtype=F32, moe_experts_held=4, moe_first_expert=2)
# six layers, ssm ssm attention twice over; chunks of 8 tokens
SSM = TransformerConfig.tiny_ssm_hybrid(dtype=F32)
# F(dense) | W W F W, a window of 8 rows, 8 experts (2 a token)
SWA = TransformerConfig.tiny_swa_moe(dtype=F32)
# K(dense) K K F K F, chunks of 8, 8 experts (2 a token) and a shared one
KDA = TransformerConfig.tiny_kda_moe(dtype=F32)
# M W M W | M F | G X: 128 channels over a state of 16, a window of 8 rows,
# four differential heads over two pairs of KV heads
SAMBAY = TransformerConfig.tiny_sambay(dtype=F32)
# three layers, all "eva": windows of 32 tokens in chunks of 4, 3 heads
EVA = TransformerConfig.tiny_eva(dtype=F32)
# M E M * E M E, one branch a layer: 2 B/C groups and 2 norm groups, 16
# experts in a latent of 32 (3 a token) of which the stack holds 4..7
SSM_MOE = TransformerConfig.tiny_ssm_moe(dtype=F32)
_SERVED = ("model.decode_step_ms", "device.idle_share.serve",
           "engine.kv_read_share")
# a prefill of 21 tokens in a bucket of 32, then 12 decode steps (the
# ablations of a state name these: ``state_at_bucket_end``, ``drop_conv_tail``)
_REFUSED = Through({0: 21}, 1, 64, 32, 12)

MODELS = {
    "mla": Model(
        cfg=MLA, ref=reference_mla_moe, hp=_latent_hp(MLA),
        foreign=("ray_tpu", "pallas"),
        tol=1e-4, metric=1,
        stacks={"dense_layers": {"ln1", "ln2", "attn", "mlp"},
                "layers": {"ln1", "ln2", "attn", "moe"}},
        shapes={"dense_layers/mlp/wi": (1, 64, 160),
                "layers/moe/wi": (2, 8, 64, 48)},
        counters=("moe_weight_visits",), state=None,
        # three slots at different depths, one of them crossing a chunk
        # edge of the decode walk (256 rows), a parked lane among them
        through={"lanes_across_a_chunk": Through(
            {0: 250, 2: 31, 3: 120}, 4, 320, 256, 9),
                 # as the 2,048 bucket is served (ISSUE 59)
                 "scores_too_large_for_one_product": Through(
                     {1: 120}, 2, 320, 256, 9, scores_at_once=False)},
        # the decode attention walks a second chunk
        generated=(270, 320),
        refused=Through({0: 200}, 1, 256, 256, 0),
        ablations=(
            {"top_k": 3}, {"no_shared": True}, {"no_scale": True},
            {"select_without_bias": True}, {"weights_with_bias": True},
            {"unrotated_k": True}, {"fp8_weights": True}),
        floor=3e-4, ablated_state=0,
        cell=Cell(
            "serve-glm-reason-saturated", "serve_mla_moe",
            "reason-saturated", ("tpot_p50_ms", "setup_s"),
            ("engine.moe_expert_read_share", "model.moe_load_imbalance",
             "kernel.decode_hbm_share.mla_moe", "model.moe_time_share",
             "model.mla_time_share", "model.prefill_expert_time_share"),
            _mla_rehearsed,
            # 0.8 requests/s where the cell offers 4.55: each finds a slot,
            # so that none is left to prefill after the window on a host
            # where an admission takes a second (tests/conftest.py); and a
            # window whose second half holds several decode blocks even
            # with six test workers on the cores: the counters' readers
            # divide what was retired between its middle and its end
            slower=0.8, seconds=16)),
    "dsa": Model(
        cfg=DSA, ref=reference_dsa_moe, hp={
            **_latent_hp(DSA), "index_topk": DSA.index_topk,
            "indexer_types": DSA.indexer_types,
            "first_expert": DSA.moe_first_expert},
        foreign=PROGRAM,
        tol=1e-4, metric=1,
        stacks={"dense_layers": {"ln1", "ln2", "attn", "mlp"},
                "layers": {"ln1", "ln2", "attn", "moe"}},
        # the indexer of each layer that owns one and the HELD experts
        shapes={"dense_layers/attn/indexer/wq": (1, 24, 4, 16),
                "layers/attn/indexer/wq": (1, 24, 4, 16),
                "layers/moe/wi": (5, 4, 64, 48),
                "layers/moe/router": (5, 64, 8)},
        counters=("dsa_rows_scored", "dsa_rows_selected", "dsa_rows_live"),
        state=None,
        # lanes shorter than index_topk (9; it grows past it), equal to it
        # (16), several times it (120, 250), a parked lane
        through={"lanes_on_both_sides_of_index_topk": Through(
            {0: 250, 2: 9, 3: 16, 1: 120}, 5, 320, 256, 8)},
        generated=(30, 64),
        refused=Through({0: 200}, 1, 256, 256, 0),
        ablations=(
            {"no_selection": True}, {"index_topk": DSA.index_topk // 2},
            {"shared_chooses_afresh": True}, {"no_relu": True},
            {"unrotated_index_k": True}, {"no_index_layernorm": True},
            {"weights_over_held": True}, {"fp8_weights": True}),
        floor=1e-2, ablated_state=0,
        cell=Cell(
            "serve-glm52-longdoc-steady", "serve_dsa_moe", "longdoc-steady",
            # tpot_p50_ms is printed in the note, not judged: it spread
            # 8-10 % over the builder's two sets of six (PERF.md section 6)
            ("ttft_p50_ms", "setup_s"),
            ("engine.attn_select_share", "model.dsa_time_share",
             "model.prefill_dsa_time_share",
             "kernel.decode_hbm_share.dsa_moe"), _dsa_rehearsed)),
    "ssm": Model(
        cfg=SSM, ref=reference_ssm, hp={
            "n_heads": SSM.n_heads, "n_kv_heads": SSM.kv_heads,
            "d_head": SSM.d_head, "eps": SSM.norm_eps,
            "embed_scale": SSM.embed_scale,
            "residual_scale": SSM.residual_scale,
            "logit_scale": SSM.logit_scale, "attn_scale": SSM.attn_scale,
            "layer_types": SSM.layer_types, "ssm_heads": SSM.ssm_heads,
            "ssm_head_dim": SSM.ssm_head_dim, "ssm_state": SSM.ssm_state,
            "ssm_groups": SSM.ssm_groups},
        foreign=PROGRAM,
        tol=2e-4, metric=0,
        stacks={"layers": {"ln1", "ln2", "attn", "mlp"},
                "ssm_layers": {"ln1", "ln2", "ssm", "mlp"}},
        shapes={"layers/ln1/scale": (2, 64),
                "ssm_layers/ln1/scale": (4, 64)},
        counters=(), state=("ssm", 2e-4),
        through={
            **{name: Through({1: n}, 3, 64, bucket, 11)
               for name, n, bucket in (
                   ("above_a_chunk", 13, 16), ("chunks", 21, 32),
                   ("a_bucket", 32, 32))},
            # the grouped full layers through the kernel, as the buckets
            # from 1,024 on are served (ISSUE 59: one rule for every family)
            "scores_too_large_for_one_product": Through(
                {1: 21}, 3, 64, 32, 11, scores_at_once=False)},
        generated=(11, 32), refused=_REFUSED,
        ablations=(
            {"state_bf16": True}, {"state_at_bucket_end": (21, 32)},
            {"drop_conv_tail": 21}, {"residual_one": True},
            {"usual_attn_scale": True}),
        floor=1e-3, ablated_state=0,
        cell=Cell(
            "serve-granite-agent-saturated", "serve_ssm", "agent-saturated",
            ("tpot_p50_ms", "setup_s"),
            ("model.ssm_time_share", "model.prefill_ssm_scan_share",
             "engine.state_live_share", "kernel.decode_hbm_share.ssm")
            + _SERVED, _ssm_rehearsed)),
    "swa": Model(
        cfg=SWA, ref=reference_swa_moe, hp={
            "n_heads": SWA.n_heads,
            "kv_heads": {"F": SWA.mha_kind(False)[0],
                         "W": SWA.mha_kind(True)[0]},
            "theta": {"F": SWA.mha_kind(False)[1],
                      "W": SWA.mha_kind(True)[1]},
            "d_head": SWA.d_head, "rotary_dim": SWA.rotary_dim,
            "window": SWA.window, "value_scale": SWA.value_scale,
            "eps": SWA.norm_eps, "top_k": SWA.moe_top_k,
            "route_scale": SWA.moe_route_scale,
            "first_expert": SWA.moe_first_expert,
            "layer_types": SWA.layer_types,
            "n_dense_layers": SWA.n_dense_layers},
        foreign=PROGRAM,
        tol=2e-4, metric=0,
        stacks={"dense_layers": {"ln1", "ln2", "attn", "mlp"},
                "layers": {"ln1", "ln2", "attn", "moe"},
                "window_layers": {"ln1", "ln2", "swa", "moe"}},
        shapes={"window_layers/swa/sink": (3, 4),
                "window_layers/swa/wk": (3, 64, 4, 64),
                "layers/attn/wv": (1, 64, 2, 32)},
        counters=("window_rows_read",), state=None,
        # 20 decode steps: the ring of 8 rows wraps at least twice
        through={name: Through({1: n}, 2, 160, bucket, 20)
                 for name, n, bucket in (
                     ("below_the_window", 5, 8), ("at_the_window", 8, 8),
                     ("above", 13, 16), ("far_above", 29, 32),
                     ("many_windows", 100, 128))},
        generated=(11, 32), refused=_REFUSED,
        ablations=(
            {"window": 7}, {"window": 9}, {"no_sink": True},
            {"sink_on_full": True}, {"no_value_scale": True},
            {"swap_theta": True}, {"rotary_all": True},
            {"window_grouping": True}, {"window_attends_all": True},
            {"fp8_weights": True}),
        floor=1e-3, ablated_state=0,
        cell=Cell(
            "serve-mimo-codeagent-saturated", "serve_swa_moe",
            "codeagent-saturated", ("tpot_p50_ms", "setup_s"),
            ("model.window_attn_time_share", "model.full_attn_time_share",
             "model.prefill_window_attn_share",
             "model.prefill_full_attn_share", "engine.window_rows_share",
             "kernel.decode_hbm_share.swa_moe", "model.moe_time_share")
            + _SERVED, _swa_rehearsed)),
    "kda": Model(
        cfg=KDA, ref=reference_kda_moe, hp={
            **_latent_hp(KDA),
            "first_expert": KDA.moe_first_expert,
            "layer_types": KDA.layer_types,
            "n_dense_layers": KDA.n_dense_layers,
            "kda_heads": KDA.kda_heads, "kda_head_dim": KDA.kda_head_dim},
        foreign=PROGRAM,
        tol=2e-4, metric=0,
        stacks={"dense_layers": {"ln1", "ln2", "kda", "mlp"},
                "kda_layers": {"ln1", "ln2", "kda", "moe"},
                "layers": {"ln1", "ln2", "attn", "moe"}},
        shapes={"kda_layers/kda/wqkv": (3, 64, 3 * 32),
                "layers/attn/wq": (2, 64, 4, 20),
                "layers/moe/shared/wi": (2, 64, 48)},
        counters=("moe_weight_visits",), state=("kda", 1e-4),
        through={
            **{name: Through({1: n}, 2, 96, bucket, 12)
               for name, n, bucket in (
                   ("under_the_taps", 2, 8), ("below_a_chunk", 5, 8),
                   ("a_chunk", 8, 8), ("above", 13, 16), ("chunks", 21, 32),
                   ("a_bucket", 32, 32), ("many", 43, 64))},
            # the full layers attend through the kernel, as a prompt whose
            # scores pass ``PREFILL_SCORE_BYTES`` does
            "scores_too_large_for_one_product": Through(
                {1: 37}, 2, 96, 48, 12, scores_at_once=False)},
        generated=(11, 32), refused=_REFUSED,
        ablations=(
            {"head_decay": True}, {"no_delta": True}, {"decay_after": True},
            {"beta_one": True}, {"no_l2norm": True}, {"silu_gate": True},
            {"state_bf16": True}, {"state_at_bucket_end": (21, 32)},
            {"drop_conv_tail": 21}, {"rotate_kr": True}, {"no_scale": True},
            {"no_shared": True}, {"fp8_weights": True}),
        floor=1e-3, ablated_state=-1,
        cell=Cell(
            "serve-kimi-longreason-saturated", "serve_kda_moe",
            "longreason-saturated", ("tpot_p50_ms", "setup_s"),
            ("model.kda_time_share", "model.prefill_kda_chunk_share",
             "engine.state_live_share.kda",
             "kernel.decode_hbm_share.kda_moe",
             "kernel.kda_update_roofline_share", "model.moe_time_share",
             "model.mla_time_share") + _SERVED, _kda_rehearsed)),
    "sambay": Model(
        cfg=SAMBAY, ref=reference_sambay, hp={
            "n_heads": SAMBAY.n_heads, "n_kv_heads": SAMBAY.kv_heads,
            "d_head": SAMBAY.d_head, "eps": SAMBAY.norm_eps,
            "window": SAMBAY.window, "layer_types": SAMBAY.layer_types,
            "mamba_state": SAMBAY.mamba_state,
            "mamba_dt_rank": SAMBAY.mamba_dt_rank},
        foreign=PROGRAM,
        tol=2e-4, metric=0,
        stacks={"layers": {"ln1", "ln2", "attn", "mlp"},
                "mamba_layers": {"ln1", "ln2", "mamba", "mlp"},
                "window_layers": {"ln1", "ln2", "swa", "mlp"},
                "gmu_layers": {"ln1", "ln2", "gmu", "mlp"},
                "cross_layers": {"ln1", "ln2", "cross", "mlp"}},
        shapes={"mamba_layers/mamba/a_log": (3, 16, 128),
                "mamba_layers/mamba/wxp": (3, 128, 4 + 32),
                "window_layers/swa/wo": (2, 4, 128, 64),
                "window_layers/swa/bk": (2, 4, 64),
                "layers/attn/lambda": (1, 4, 64),
                "cross_layers/cross/wq": (1, 64, 8, 64),
                "gmu_layers/gmu/wi": (1, 64, 128),
                "layers/ln1/bias": (1, 64)},
        counters=("cross_rows_read",), state=("mamba", 2e-4),
        # 20 decode steps: the ring of 8 rows wraps at least twice; the
        # prompts' lengths lie on both sides of the convolution's taps,
        # the window and the scan's time block
        through={
            **{name: Through({1: n}, 3, 160, bucket, 20)
               for name, n, bucket in (
                   ("under_the_taps", 2, 8), ("below_the_window", 5, 8),
                   ("at_the_window", 8, 8), ("above", 13, 16),
                   ("a_padded_bucket", 21, 32), ("a_bucket", 32, 32),
                   ("many_windows", 100, 128))},
            # lanes at different positions and a parked one between them
            "lanes_at_different_depths": Through(
                {0: 70, 2: 9, 3: 31}, 4, 160, 128, 12)},
        generated=(11, 32), refused=_REFUSED,
        ablations=(
            {"lambda_zero": True}, {"m_after_gate": True},
            {"state_bf16": True}, {"keep_lambda_init": True},
            {"cross_strict": True}, {"window": 7}, {"window": 9},
            {"rms_norm": True}, {"state_at_bucket_end": (21, 32)},
            {"drop_conv_tail": 21}),
        floor=1e-3, ablated_state=-1,
        cell=Cell(
            "serve-phi4flash-reason-saturated", "serve_sambay",
            "histreason-saturated", ("tpot_p50_ms", "setup_s"),
            ("model.mamba1_time_share", "model.gmu_time_share",
             "model.shared_attn_time_share", "engine.shared_rows_share",
             "model.prefill_mamba1_scan_share", "model.prefill_upper_share",
             "kernel.decode_hbm_share.sambay",
             "kernel.mamba_scan_roofline_share",
             "model.window_attn_time_share") + _SERVED,
            _sambay_rehearsed)),
    "eva": Model(
        cfg=EVA, ref=reference_eva, hp={
            "n_heads": EVA.n_heads, "d_head": EVA.d_head,
            "eps": EVA.norm_eps, "theta": EVA.rope_theta,
            "window": EVA.eva_window, "chunk": EVA.eva_chunk,
            "n_pred_heads": EVA.n_pred_heads},
        foreign=PROGRAM,
        tol=2e-4, metric=0,
        stacks={"eva_layers": {"ln1", "ln2", "eva", "mlp"}},
        shapes={"eva_layers/eva/phi": (3, 4, 16),
                "eva_layers/eva/wk": (3, 64, 4, 16),
                "lm_head": (64, 3 * 64)},
        counters=("eva_window_rows_read", "eva_summary_rows_read",
                  "eva_windows_closed"), state=None,
        # 40 decode steps: a window of 32 rows closes at least once; the
        # prompts lie on both sides of a chunk, of a window and of two
        through={
            **{name: Through({1: n}, 2, 192, bucket, 40)
               for name, n, bucket in (
                   ("below_a_chunk", 3, 8), ("below_the_window", 21, 32),
                   ("a_window", 32, 32), ("above", 45, 64),
                   ("many_windows", 100, 128))},
            # lanes at different depths and a parked one between them: they
            # close their windows at different steps
            "lanes_at_different_depths": Through(
                {0: 70, 2: 9, 3: 31}, 4, 192, 128, 30)},
        generated=(27, 64),
        # the last token lies deep in an open window, behind two closed
        refused=Through({0: 70}, 1, 128, 80, 25),
        ablations=(
            {"pool_15_of_16": True}, {"swap_phi_mu": True},
            {"open_summaries": True}, {"residual_bf16": True},
            {"pool_unrotated": True}, {"pool_unscaled": True},
            {"no_summaries": True}, {"fp8_weights": True}),
        floor=1e-3, ablated_state=0,
        cell=Cell(
            "serve-evabyte-bytedoc-saturated", "serve_eva",
            "bytedoc-saturated", ("tpot_p50_ms", "setup_s"),
            ("model.eva_time_share", "model.prefill_eva_share",
             "engine.summary_rows_share", "kernel.decode_hbm_share.eva")
            + _SERVED, _eva_rehearsed)),
    "ssm_moe": Model(
        cfg=SSM_MOE, ref=reference_ssm_moe, hp={
            "n_heads": SSM_MOE.n_heads, "n_kv_heads": SSM_MOE.kv_heads,
            "d_head": SSM_MOE.d_head, "eps": SSM_MOE.norm_eps,
            "layer_types": SSM_MOE.layer_types,
            "ssm_heads": SSM_MOE.ssm_heads,
            "ssm_head_dim": SSM_MOE.ssm_head_dim,
            "ssm_state": SSM_MOE.ssm_state,
            "ssm_groups": SSM_MOE.ssm_groups,
            "norm_groups": SSM_MOE.ssm_norm_groups,
            "top_k": SSM_MOE.moe_top_k,
            "route_scale": SSM_MOE.moe_route_scale,
            "first_expert": SSM_MOE.moe_first_expert},
        foreign=PROGRAM,
        tol=2e-4, metric=0,
        # one norm and ONE branch a layer
        stacks={"layers": {"ln1", "attn"}, "ssm_layers": {"ln1", "ssm"},
                "expert_layers": {"ln1", "moe"}},
        # the HELD experts in the latent, the whole router, the two
        # projections round the latent, the shared expert at its own width
        shapes={"expert_layers/moe/wi": (3, 4, 32, 24),
                "expert_layers/moe/wo": (3, 4, 24, 32),
                "expert_layers/moe/router": (3, 64, 16),
                "expert_layers/moe/latent_in": (3, 64, 32),
                "expert_layers/moe/latent_out": (3, 32, 64),
                "expert_layers/moe/shared/wi": (3, 64, 48),
                "ssm_layers/ssm/wxbc": (3, 64, 32 + 2 * 2 * 16),
                "ssm_layers/ssm/norm": (3, 32),
                "layers/attn/wk": (1, 64, 2, 64), "lm_head": (64, 256)},
        counters=("moe_weight_visits",), state=("ssm", 2e-4),
        through={
            **{name: Through({1: n}, 3, 64, bucket, 11)
               for name, n, bucket in (
                   ("under_the_taps", 2, 8), ("above_a_chunk", 13, 16),
                   ("chunks", 21, 32), ("a_bucket", 32, 32))},
            # lanes at different depths and a parked one between them; 4
            # lanes x 3 picks: the share's fused form
            "lanes_at_different_depths": Through(
                {0: 40, 2: 9, 3: 31}, 4, 64, 48, 10),
            "scores_too_large_for_one_product": Through(
                {1: 21}, 3, 64, 32, 11, scores_at_once=False)},
        generated=(11, 32), refused=_REFUSED,
        ablations=(
            {"state_bf16": True}, {"state_at_bucket_end": (21, 32)},
            {"drop_conv_tail": 21}, {"one_norm_group": True},
            {"route_scale_one": True}, {"relu": True}, {"top_k": 2},
            {"no_shared": True}),
        floor=1e-3, ablated_state=0,
        cell=Cell(
            "serve-nemotron3-multiagent-saturated", "serve_ssm_moe",
            "multiagent-saturated", ("tpot_p50_ms", "setup_s"),
            ("model.moe_latent_proj_share",
             "kernel.decode_hbm_share.ssm_moe",
             "kernel.grouped_matmul_roofline_share.latent",
             "model.ssm_time_share", "model.prefill_ssm_scan_share",
             "engine.state_skip_share", "model.moe_time_share",
             "model.moe_load_imbalance", "engine.moe_expert_read_share",
             "model.prefill_expert_time_share",
             "engine.prefill_live_pair_share") + _SERVED,
            _ssm_moe_rehearsed, slower=0.8, seconds=16)),
}


each_model = pytest.mark.parametrize("name", list(MODELS))


@lru_cache(maxsize=None)
def served(name):
    """(the model's row, its seeded parameters)."""
    return MODELS[name], init_params(MODELS[name].cfg, jax.random.key(0))


def tokens_of(m: Model, n, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0,
                              m.cfg.vocab_size)


def ref_logits(m: Model, params, tokens, **kw):
    """(the reference's logits [S, V] over ``tokens``, its recurrent
    states at their end, a layer each, or None)."""
    with jax.default_matmul_precision("highest"):
        out = m.ref.forward_logits(
            params, jnp.asarray(tokens, jnp.int32), m.hp, **kw)
    return out if isinstance(out, tuple) else (out, None)


def prefill(m: Model, params, cache, slot, prompt, bucket):
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(prompt)].set(prompt)
    return gen.prefill_into_slot(
        params, padded, jnp.int32(len(prompt)), jnp.int32(slot), cache,
        m.cfg)


def served_through(m: Model, params, run: Through, seed=3):
    """``run`` with given tokens (a lane's are ``tokens_of(.., seed +
    slot)``): ({slot: its tokens}, {slot: the logits of the prefill and of
    every decode step}, the cache at the end)."""
    toks = {slot: tokens_of(m, n + run.steps, seed + slot)
            for slot, n in run.lanes.items()}
    cache = gen.init_kv_cache(m.cfg, run.slots, run.s_max)
    got = {}
    for slot, n in run.lanes.items():
        logits, cache = prefill(
            m, params, cache, slot, toks[slot][:n], run.bucket)
        got[slot] = [logits]
    lanes = jnp.asarray(list(run.lanes))
    at = jnp.asarray(list(run.lanes.values()), jnp.int32)
    for step in range(run.steps):
        tok = jnp.zeros(run.slots, jnp.int32).at[lanes].set(jnp.stack(
            [toks[slot][n + step] for slot, n in run.lanes.items()]))
        pos = jnp.zeros(run.slots, jnp.int32).at[lanes].set(at + step)
        logits, cache = gen.decode_step_multi(params, tok, cache, pos, m.cfg)
        for slot in run.lanes:
            got[slot].append(logits[slot])
    return toks, got, cache


def engine_of(m: Model, params):
    from ray_tpu.serve.llm import LLMEngine

    # its own copy: the engine owns its weights (lay_out_for_decode)
    return LLMEngine(
        jax.tree.map(jnp.array, params), m.cfg, max_slots=2, max_len=64,
        prefill_buckets=(8, 16, 32))


def worst_margin(m: Model, params, prompt, ids):
    """How far the served tokens' logits lie under the reference's
    largest, teacher-forced on the served tokens (0: the same tokens)."""
    logits, _ = ref_logits(m, params, list(prompt) + list(ids[:-1]))
    if logits.ndim == 3:  # several prediction heads: the next token's
        logits = logits[:, 0]
    return float(reference_mla_moe.served_token_margin(
        logits[len(prompt) - 1:], jnp.asarray(ids, jnp.int32)).max())


# -- the description ---------------------------------------------------------

@each_model
def test_params_axes_and_count_agree(name):
    m, params = served(name)
    axes = param_logical_axes(m.cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(
        x, tuple)) == jax.tree.structure(params)
    for a, p in zip(jax.tree.leaves(axes, is_leaf=lambda x: isinstance(
            x, tuple)), jax.tree.leaves(params)):
        assert len(a) == p.ndim
    assert m.cfg.param_count() == sum(
        p.size for p in jax.tree.leaves(params))
    stacks = {k: set(v) for k, v in params.items() if k.endswith("layers")}
    assert stacks == m.stacks
    for path, shape in m.shapes.items():
        leaf = params
        for key in path.split("/"):
            leaf = leaf[key]
        assert leaf.shape == shape, path
    keys = gen.block_stat_keys(m.cfg)
    assert keys[len(keys) - len(m.counters):] == m.counters


# -- the forward and the two programs against the reference ------------------

@each_model
def test_the_uncached_forward_matches_the_reference(name):
    m, params = served(name)
    toks = tokens_of(m, 70 if m.state is None else 37)
    if m.cfg.index_topk:  # a block that selects runs on the serving paths
        with pytest.raises(NotImplementedError):
            forward(params, toks[None], m.cfg)
        return
    got = forward(params, toks[None], m.cfg)[0]
    want, _ = ref_logits(m, params, toks)
    assert float(reference_mla_moe.vector_distance(got, want)[m.metric]) < m.tol


@pytest.mark.parametrize("name,case", [
    (name, case) for name, m in MODELS.items() for case in m.through],
    ids=lambda v: v)
def test_prefill_and_decode_through_a_slot_match_the_reference(
        name, case, monkeypatch):
    """Padded prompts into their slots, then decode steps with the other
    lanes parked: every step's logits against the reference's ONE full
    forward over prompt + answer (the model is causal), a recurrent state
    at the end against the reference's, and a parked lane's still empty."""
    m, params = served(name)
    run = m.through[case]
    if not run.scores_at_once:
        monkeypatch.setattr(attention, "PREFILL_SCORE_BYTES", 0)
    toks, got, cache = served_through(m, params, run)
    for slot, n in run.lanes.items():
        want, want_states = ref_logits(m, params, toks[slot])
        for step, logits in enumerate(got[slot]):
            assert float(reference_mla_moe.vector_distance(
                logits, want[n - 1 + step])[m.metric]) < m.tol, (slot, step)
        if m.state:
            leaf, bound = m.state
            states = gen.cache_state(cache)[leaf]
            for i, state in enumerate(want_states):
                assert float(m.ref.state_distance(
                    states[i, slot], state)) < bound
            parked = [s for s in range(run.slots) if s not in run.lanes]
            assert not np.asarray(states[:, parked]).any()


@each_model
def test_prefill_leaves_the_other_slots_bit_identical(name):
    m, params = served(name)
    cache = gen.init_kv_cache(m.cfg, 3, 64)
    _, cache = prefill(m, params, cache, 1, tokens_of(m, 19, 2), 32)
    before = jax.tree.map(lambda a: np.asarray(a[:, 1]), cache)
    _, cache = prefill(m, params, cache, 0, tokens_of(m, 14, 3), 16)
    _, cache = prefill(m, params, cache, 2, tokens_of(m, 5, 4), 16)
    after = jax.tree.map(lambda a: np.asarray(a[:, 1]), cache)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert np.array_equal(a, b)
    for leaf in jax.tree.leaves(before):  # and slot 1 holds its prompt
        assert leaf.any()


# -- the engine ---------------------------------------------------------------

@each_model
def test_a_reused_slot_gives_the_tokens_of_a_fresh_engine(name):
    m, params = served(name)
    p, q = np.asarray(tokens_of(m, 17, 7)), np.asarray(tokens_of(m, 6, 8))
    eng = engine_of(m, params)
    try:
        eng.generate(p, max_new_tokens=9)  # slot 0 full, then freed, parked
        eng.generate(p, max_new_tokens=3)  # parked lanes step meanwhile
        again = eng.generate(q, max_new_tokens=8)  # a used slot
    finally:
        eng.shutdown()
    fresh = engine_of(m, params)
    try:
        assert again == fresh.generate(q, max_new_tokens=8)
    finally:
        fresh.shutdown()
    assert worst_margin(m, params, q, again) < m.tol


@each_model
def test_generate_runs_the_served_programs(name):
    """``generate()`` runs the engine's two programs, so it generates
    every block the way it is served: two rows of one length."""
    m, params = served(name)
    n, max_len = m.generated
    prompt = jnp.stack([tokens_of(m, n, 3), tokens_of(m, n, 4)])
    ids = gen.generate(params, prompt, m.cfg, max_new_tokens=12,
                       max_len=max_len)
    assert ids.shape == (2, 12)
    for b in range(2):
        assert worst_margin(m, params, np.asarray(prompt[b]),
                            np.asarray(ids[b]).tolist()) < m.tol


# -- what a comparison must refuse -------------------------------------------

@lru_cache(maxsize=None)
def refused_run(name):
    """The served path of the model's ``refused`` run: its tokens, the
    last step's logits and the state the comparison looks at."""
    m, params = served(name)
    toks, got, cache = served_through(m, params, m.refused, seed=11)
    state = (gen.cache_state(cache)[m.state[0]][m.ablated_state, 0]
             if m.state else None)
    return toks[0], got[0][-1], state


@pytest.mark.parametrize("name,ablate", [
    (name, ablate) for name, m in MODELS.items() for ablate in m.ablations],
    ids=lambda v: v if isinstance(v, str) else "%s_%s" % next(
        iter(v.items())))
def test_each_ablation_fails_the_comparison(name, ablate):
    """The served path equals the reference and differs from each
    deliberately wrong one: by the last logits, or (a state kept in bf16)
    by a layer's state."""
    m, params = served(name)
    toks, last, state = refused_run(name)

    def distance(**kw):
        want, states = ref_logits(m, params, toks, **kw)
        far = float(reference_mla_moe.vector_distance(last, want[-1])[1])
        if m.state:
            far = max(far, float(m.ref.state_distance(
                state, states[m.ablated_state])))
        return far

    assert distance() < m.tol < m.floor < distance(ablate=ablate)


@each_model
def test_the_reference_names_none_of_the_programs_code(name):
    """The benchmark's file has its marker line once (above it: the
    docstring, and the import of the reference it builds on), and below it
    names none of the program's code, so that what decides ``correct`` can
    share no fault with what it judges."""
    m = MODELS[name]
    marker = "# ---- below this line the two copies are identical ----\n"
    assert os.path.dirname(m.ref.__file__) == os.path.join(ROOT, "benchmarks")
    with open(m.ref.__file__) as f:
        text = f.read()
    assert text.count(marker) == 1
    body = text.split(marker)[1]
    for name in m.foreign:
        assert name not in body  # none of the program's code


# -- the benchmark resolves and rehearses the model's cell -------------------

@pytest.fixture(scope="module")
def listed():
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--list"], cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return [json.loads(line) for line in out.stdout.splitlines()]


@each_model
def test_the_list_resolves_the_cell(name, listed):
    m = MODELS[name]
    assert len(listed) >= 9  # later PRs add cells
    row = next(r for r in listed if r["cell"] == m.cell.name)
    assert (row["runner"], row["traffic"], row["chips"]) == (
        m.cell.runner, m.cell.traffic, 1)
    assert tuple(row["end_to_end"]) == m.cell.end_to_end
    for name in m.cell.per_layer:
        assert name in row["per_layer"]


@pytest.mark.phase_limit(900)  # a minute alone; six workers share the cores
@each_model
def test_the_cell_rehearses_on_the_host_with_every_reader_walked(
        name, rehearsal_manifest):
    cell = MODELS[name].cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    mine = [m["name"] for m in doc["per_layer"]
            if cell.name in m.get("workloads", ())]
    for reader in cell.per_layer:
        assert reader in mine
    slower = ["--manifest", rehearsal_manifest(cell.traffic, cell.slower)
              ] if cell.slower else []
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", *slower, "--workload",
         cell.name, "--seed", str(2 ** 31 + 7), "--seconds",
         str(cell.seconds), "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=870,
        # the suite's eight virtual host devices are not the cell's one
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert out.returncode == 10, out.stdout[-3000:] + out.stderr[-3000:]
    walked = next(line for line in out.stdout.splitlines()
                  if line.startswith("readers walked"))
    values = json.loads(walked.split(": ", 1)[1])
    assert sorted(values) == sorted(mine)
    note = next((json.loads(line)["note"] for line in out.stdout.splitlines()
                 if line.startswith('{"note"')), None)
    cell.rehearsed(values, note)
