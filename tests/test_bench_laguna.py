"""The Laguna configuration, traffic, arithmetic and metric files the
benchmark gained in PR 52, under every PR's tests: the cases live beside the
code they pin."""

from benchmarks.tests.test_bench_laguna import *  # noqa

import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(*command):
    out = subprocess.run(
        [sys.executable, *command], capture_output=True, text=True,
        timeout=600,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_new_cell_rehearses_on_the_cpu_with_its_trace():
    """``benchmarks/selftest.py --rehearse`` names its cells and may not be
    edited by the PR that adds one (the benchmark's files are add-only), so
    the new cell's rehearsal lives here, ``selftest.py --rehearse``'s way:
    serve -> proxy -> ``LLMServer`` -> ``JaxLLMEngine`` at tiny widths on CPU
    workers, traced, with the harness's two-layer reference check (whose 64
    + 3 positions wrap the tiny ring of 8 eight times), ending in a line
    that cannot be mistaken for a run."""
    last = run(os.path.join(REPO, "benchmarks", "run.py"), "--workload",
               "laguna_ep16_code_closed32", "--seed", "5200000019",
               "--seconds", "3", "--trace", "1", "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and last["attempted"] > 0
    assert last["failed"] == 0 and not last["problems"]
    assert not {"metrics", "correct", "device"} & set(last), last


def test_the_builders_comparison_rehearses_on_the_cpu():
    """``benchmarks/laguna_all_layers.py``: all the layers through the
    engine's own programs with rows beyond the window and rows that cross
    it while they decode, the reference's three switched-off controls and
    the coarse matrices, walked at tiny widths (where the scales leave the
    limit without meaning)."""
    last = run(os.path.join(REPO, "benchmarks", "laguna_all_layers.py"),
               "--rehearse-cpu")
    assert last["rehearsal_ok"] is True and "ok" not in last
    assert last["positions"] == 20 and last["layers"] == 5
    long_rows, short_rows = last["lengths"][:2], last["lengths"][2:]
    assert min(long_rows) > 8 * 8 and max(short_rows) < 8 < (
        min(short_rows) + last["steps"])
    for name in ("program", "control_no_gate", "control_no_yarn_long_rows",
                 "control_window_an_eighth_too_wide_long_rows",
                 "reported_window_one_too_wide_long_rows",
                 "control_coarse_matrices"):
        assert 0 < last[name]["median_rms"] <= last[name]["worst_rms"]
    assert last["attention_layer0"]["positions"] == long_rows[0]


@pytest.mark.parametrize("seed", [7, 5200000087, 5200000089, 2 ** 31 + 5])
def test_a_seeds_prompts_load_the_held_experts_as_the_deployment_does(seed):
    """``families/laguna.py`` ``SCALES`` (5), (6): a prompt is the generator's
    66 characters and BOS and a router reads the token alone, so the share of
    a prompt's choices that falls on the experts held here is those 67 ids'
    draw; the family takes, of ``ROUTER_DRAWS`` draws from the seed, the
    routers under which it is nearest the routed share (here 8 of 16
    experts: one half; an unconditioned draw of 67 x 4 choices x 4 layers
    misses by 1.5 % of it, one standard deviation), in the routers' own
    channels and nowhere else; and the head's row for the stop id is zero,
    so no greedy stream ends before its ``max_tokens``."""
    import numpy as np

    from benchmarks.families import laguna as family
    from benchmarks.lib import traffic
    from ray_tpu.llm.tokenizer import ByteTokenizer

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna_s21_l9_ep16.json")) as f:
        model = json.load(f)["tiny"]
    cfg = family.config(model)
    params = family.load_params(model, seed)
    own = cfg.d_model // family.SCALES["router_share"]
    router = np.asarray(params["blocks"]["moe"]["router"])
    assert router[:, :own].any() and not router[:, own:].any()
    ids = sorted({ByteTokenizer.BOS, *traffic.PRINTABLE.encode()})
    logits = np.einsum("vc,lce->lve", np.asarray(
        params["wte"], np.float32)[ids, :own], router[:, :own])
    chosen = np.argsort(-logits, -1)[..., :cfg.top_k]
    held = ((chosen >= cfg.expert_offset)
            & (chosen < cfg.expert_offset + cfg.experts_held)).mean()
    assert abs(held - cfg.experts_held / cfg.n_routed_experts) < 0.004
    assert not np.asarray(params["lm_head"])[ByteTokenizer.EOS].any()
    assert np.asarray(params["lm_head"])[ByteTokenizer.EOS - 1].any()
