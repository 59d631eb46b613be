"""``tools/prefill_attention_micro.py`` and ``tools/admission_profile.py``
are tools for the chip (the tables behind ``ops/attention.
PREFILL_SCORE_BYTES``); here each is imported and walked at a toy size, the
kernel through the Pallas interpreter, so that the next PR that has to
print a table again finds them working. A time off the chip is no device
number: none is reported."""

import importlib.util
import json
import os

import pytest

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
_TIMES = {"ms_a_call", "program_ms", "kernel_ms", "tflops_computed",
          "peak_share_computed", "peak_share_useful"}


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_TOOLS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _rows(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


def test_the_slot_table_walks_every_form_against_the_dense_one(
        capsys, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)  # the tool writes chiprun_out/ where it runs
    _tool("prefill_attention_micro").main(["--slot", "--tiny"])
    rows = _rows(capsys)
    assert {r["blocks"] for r in rows} == {"rule", "16x16", "16x48"}
    assert {(r["tokens"], r["prompt_len"]) for r in rows} == {
        (48, 30), (96, 70)}
    for r in rows:  # the slot's dense form is the answer, zeros past it
        assert r["err"] < 2e-2 and r["past_length_all_zero"]
        assert not _TIMES & set(r)


def test_the_eva_table_walks_the_kernel_beside_the_block_loop(
        capsys, tmp_path, monkeypatch):
    """EvaByte's windows at a toy size: every blocks' kernel gives the
    block loop's answer up to the prompt and zeros past it, computes no
    fewer pairs than the prompt needs and, with blocks of a quarter of a
    window, fewer than the block loop's whole windows and every summary."""
    monkeypatch.chdir(tmp_path)
    _tool("prefill_attention_micro").main(["--eva", "--tiny"])
    rows = _rows(capsys)
    assert {r["blocks"] for r in rows} == {"rule", "8x8", "8x16x16"}
    assert {(r["tokens"], r["prompt_len"]) for r in rows} == {
        (48, 48), (48, 40), (96, 96), (96, 81)}
    for r in rows:
        assert r["form"] == "kernel" and r["geometry"] == "eva"
        assert r["err"] < 2e-2 and r["past_length_all_zero"]
        assert r["pairs_useful"] <= r["pairs_computed"]
        if r["blocks"] == "8x8":  # windows of 32 in chunks of 4
            assert r["pairs_computed"] < r["tokens"] * (
                32 + r["tokens"] // 32 * 8)
        assert not _TIMES & set(r)
    small = {r["prompt_len"]: r["pairs_computed"] for r in rows
             if r["blocks"] == "8x8" and r["tokens"] == 96}
    assert small[81] < small[96]  # the padding's blocks are skipped


@pytest.mark.parametrize("config,score_bytes,buckets", [
    ("gptj-6b-int8-serve", (), [64, 128, 256, 512, 1024]),
    ("granite4-h-micro-bf16-serve", (0, 10 ** 12), [64, 128]),
    ("evabyte-l8-bf16-serve", (), [64, 128])])
def test_the_admission_profile_walks_a_configurations_buckets(
        capsys, monkeypatch, config, score_bytes, buckets):
    """Every bucket, each form in its turn (a form's program is compiled
    once and held while the constant moves on), a line a profile."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(
        attention, "PREFILL_SCORE_BYTES", attention.PREFILL_SCORE_BYTES)
    forms = list(score_bytes) or [attention.PREFILL_SCORE_BYTES]
    argv = ["--config", config, "--tiny", "--repeats", "2"]
    if score_bytes:
        argv += ["--score-bytes", *map(str, score_bytes)]
    _tool("admission_profile").main(argv)
    rows = _rows(capsys)
    assert [(r["bucket"], r["repeat"], r["score_bytes"]) for r in rows] == [
        (b, i, f) for b in buckets for i in range(2) for f in forms]
    for r in rows:
        assert r["prompt_len"] == int(r["bucket"] * 0.8)
        assert not _TIMES & set(r)
