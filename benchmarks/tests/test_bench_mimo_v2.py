"""What PR 45 added to the yardstick, pinned on the CPU: the MiMo-V2
configuration and traffic files, ``lib/flops_mimo_v2.py``'s arithmetic and
the four new metric files on readers that were there
(``family_decode_roofline``, ``per_held_expert``, ``module_ms``) on hand-built
spans.  Pure functions and files: no device, no timing.
"""

import importlib
import json
import os
import types

import pytest

from benchmarks.lib import flops, flops_mimo_v2 as fl
from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "mimo25_ep16_mixed_closed64"
# What may differ from the source: the cuts, and nothing that is a width.
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_METRICS = ["windowed_decode_roofline.serve", "ep16_expert_tokens.serve",
               "ep16_experts_touched_pct.serve", "prefill_ms.serve_rate"]


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(HERE, "configs", "mimo_v25_l7_ep16.json")


@pytest.fixture(scope="module")
def mix():
    return load(HERE, "traffic", "mixed_closed64.json")


def test_the_configuration_is_the_source_but_for_its_three_cuts(config):
    published = config["published"]
    assert config["reduced"] == REDUCED
    assert {k for k in published if config[k] != published[k]} == set(REDUCED)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (7, 16, 19072)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("assumed", "deployment", "memory", "reduced_why"):
        assert config[key]
    # The program's config at the published widths, key by key.
    m = config["model"]
    attn = "".join("FW"[k] for k in published["hybrid_layer_pattern"])
    mlp = "".join("DE"[k] for k in published["moe_layer_freq"])
    assert len(attn) == len(mlp) == published["num_hidden_layers"] == 48
    # layer 0 (the leading dense layer, once) + layers 6-11: a whole period
    assert m["attn_pattern"] == attn[0] + attn[6:12] == "FWWWWWF"
    assert m["mlp_pattern"] == mlp[0] + mlp[6:12] == "DEEEEEE"
    assert m["n_layer"] == 7
    assert attn[6:12] * 7 == attn[6:]  # the period, to the model's end
    same = {"d_model": "hidden_size", "n_head": "num_attention_heads",
            "n_kv_head": "num_key_value_heads",
            "n_kv_head_window": "swa_num_key_value_heads",
            "head_dim": "head_dim", "v_head_dim": "v_head_dim",
            "rope_theta": "rope_theta", "rope_theta_window": "swa_rope_theta",
            "window": "sliding_window", "value_scale": "attention_value_scale",
            "d_ff": "intermediate_size", "d_expert": "moe_intermediate_size",
            "n_routed_experts": "n_routed_experts",
            "top_k": "num_experts_per_tok", "rms_eps": "layernorm_epsilon"}
    assert {k: m[k] for k in same} == {k: published[v]
                                       for k, v in same.items()}
    assert m["rotary_dim"] == int(
        published["head_dim"] * published["partial_rotary_factor"]) == 64
    assert (published["swa_head_dim"], published["swa_v_head_dim"],
            published["swa_num_attention_heads"]) == (
                m["head_dim"], m["v_head_dim"], m["n_head"])
    assert (m["experts_held"], m["vocab_size"]) == (16, 19072)
    fam = importlib.import_module("benchmarks.families." + config["family"])
    for name in ("model", "tiny"):
        cfg = fam.config(config[name])
        # the harness's two-layer check runs both attentions and both MLPs
        assert (cfg.attn_kinds[:2], cfg.mlp_kinds[:2]) == ("FW", "DE")
        assert cfg.experts_held * 2 <= cfg.n_routed_experts
    # the rehearsal's 64 + 3 positions wrap the tiny ring; the chip's cannot
    assert config["tiny"]["window"] * 8 <= 64 < m["window"]
    bench = load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]


def test_the_traffic_fits_the_cell_and_falls_on_every_rung(config, mix):
    sizes = traffic.sizes(mix)
    eng = config["engine"]
    assert len(sizes) == mix["arrivals"]["clients"] == eng["max_batch_size"]
    assert max(p + o for p, o in sizes) == 3405 < eng["max_seq_len"] - 1
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            < eng["max_seq_len"] - 1)
    rungs = [sum(1 for p, _ in sizes if lo < p <= hi) for lo, hi in (
        (0, 256), (256, 512), (512, 1024), (1024, 2048), (2048, 4096))]
    assert rungs == [17, 15, 16, 9, 7]
    # all but the shortest prompts cross the window in prefill
    window = config["model"]["window"]
    assert sum(p <= window for p, _ in sizes) <= 8
    # every stream leaves the window behind and wraps its rings
    assert min(p + o for p, o in sizes) == 232 > window
    assert 920 < flops.mean_decode_context(sizes) < 930
    tiny = dict(mix, **mix["tiny"])
    assert max(p + o for p, o in traffic.sizes(tiny)) < (
        config["tiny_engine"]["max_seq_len"] - 1)
    a, b = traffic.requests(mix, 4500000019), traffic.requests(mix, 7)
    assert a != b and sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)


def test_the_parameter_count_is_the_published_models(config):
    """ISSUE 45's count: a window layer's attention 94.4 M, a full layer's
    89.1 M, a router 1.05 M, one expert 25.17 M, the dense MLP 201.3 M; the
    whole model 308.8 B (the published 309B)."""
    pub = dict(config["model"], n_layer=48, experts_held=256,
               vocab_size=config["published"]["vocab_size"],
               attn_pattern="F" + "WWWWF" + "WWWWWF" * 7,
               mlp_pattern="D" + "E" * 47)
    assert fl.attention_params(pub, "W") == 4096 * (
        64 * 192 + 8 * 192 + 8 * 128) + 64 * 128 * 4096
    assert round(fl.attention_params(pub, "W") / 1e6, 1) == 94.4
    assert round(fl.attention_params(pub, "F") / 1e6, 1) == 89.1
    assert round(fl.router_params(pub) / 1e6, 2) == 1.05
    assert fl.expert_params(pub) == 3 * 4096 * 2048 == 25165824
    assert round(fl.dense_mlp_params(pub) / 1e6, 1) == 201.3
    assert round(fl.model_params(pub, 256) / 1e9, 1) == 308.8
    # active a token: eight experts of every expert layer
    assert round(fl.model_params(pub, 8) / 1e9, 1) == 15.4


def test_a_decode_steps_bytes_and_operations_from_the_cells_shapes(config):
    m = config["model"]
    assert (fl.attn_kinds(m), fl.mlp_kinds(m)) == ("FWWWWWF", "DEEEEEE")
    assert fl.held_expert_slots(m) == 96
    # 2 full + 5 window attentions, the dense MLP and the head in bf16, six
    # float32 routers: 1.88 GB
    assert round(fl.nonexpert_weight_bytes(m) / 1e9, 2) == 1.88
    assert fl.kv_bytes_per_position(m, "F") == 2 * 4 * 320 == 2560
    assert fl.kv_bytes_per_position(m, "W") == 2 * 8 * 320 == 5120
    # a slot inside the window reads what it has; beyond it the rings stop
    assert fl.cache_bytes_per_slot(m, 100) == 100 * (2 * 2560 + 5 * 5120)
    assert fl.cache_bytes_per_slot(m, 924) == 924 * 5120 + 5 * 128 * 5120
    step = fl.decode_step_bytes(m, {"experts_touched": 83.5}, 63, 924.0)
    assert step == pytest.approx(
        fl.nonexpert_weight_bytes(m) + 83.5 * 2 * 25165824
        + 63 * (924 * 5120 + 5 * 128 * 5120))
    assert 7.5 < step / 819e9 * 1e3 < 8.5  # ms at the v5e's bandwidth
    # idle: no slot, no expert: the weights outside the experts alone
    assert fl.decode_step_bytes(m, {"experts_touched": 0.0}, 0, 0.0) == (
        fl.nonexpert_weight_bytes(m))
    per_token = fl.decode_flops_per_token(m, 924.0)
    dense = (2 * fl.attention_params(m, "F") + 5 * fl.attention_params(m, "W")
             + fl.dense_mlp_params(m) + 6 * fl.router_params(m))
    assert per_token == pytest.approx(
        2 * (dense + 6 * 25165824 * 8 / 16 + 19072 * 4096)
        + 2 * (2 * 924 + 5 * 128) * 64 * 320)
    assert 2.0e9 < per_token < 2.3e9


# Two decode steps of a full batch and an idle tail, as the engine writes
# its counts (one step late) on zero-length spans; one decode program of 12
# ms, prefill rungs of 10 and 40 ms.
COUNTS = [
    {"occupied": 64, "waiting": 0, "admitted": 0, "retired": 0,
     "host_syncs": 1, "routed_total": 3072, "routed_held": 200,
     "experts_touched": 85},
    {"occupied": 62, "waiting": 0, "admitted": 1, "retired": 1,
     "host_syncs": 2, "routed_total": 2976, "routed_held": 184,
     "experts_touched": 82, "trace_id": "abc"},
]
HOST = [[["engine.step", 0, 100, {"seq": 0}],
         ["engine.counts", 90, 0, COUNTS[0]],
         ["engine.step", 200, 100, {"seq": 1}],
         ["engine.counts", 290, 0, COUNTS[1]]]]
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 50]],
    tr.MODULES_LINE: [["jit__lambda(1)", 0, 12_000_000],
                      ["jit__lambda(2)", 30_000_000, 12_000_000],
                      ["jit_prefill_one(3)", 60_000_000, 10_000_000],
                      ["jit_prefill_one(4)", 80_000_000, 40_000_000],
                      ["jit_prefill_one(5)", 130_000_000, 12_000_000]]}}


def ctx_of(config, mix, family=None):
    spans = hs.from_planes(HOST, DEVICE)
    return types.SimpleNamespace(
        host_spans=[spans], trace=tr.Trace.from_planes(DEVICE),
        config=dict(config, family=family or config["family"]), mix=mix,
        stats={"model": config["model"]},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def read_metric(name, ctx):
    """A metric file's reader on its own arguments, as ``run.py`` calls it."""
    spec = load(HERE, "layer_metrics", name + ".json")
    assert spec["name"] == name and spec["what"]
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    return reader.read(ctx, **spec["args"])


def test_the_four_new_metric_files_read_hand_built_spans(config, mix):
    ctx = ctx_of(config, mix)
    assert read_metric("ep16_expert_tokens.serve", ctx) == pytest.approx(
        (200 + 184) / 2 / 96)  # 2.0
    assert read_metric(
        "ep16_experts_touched_pct.serve", ctx) == pytest.approx(
            100 * 83.5 / 96)  # 87 %
    assert read_metric("prefill_ms.serve_rate", ctx) == 12.0  # the median
    got = read_metric("windowed_decode_roofline.serve", ctx)
    want = fl.decode_step_bytes(
        config["model"], {"experts_touched": 83.5}, 63.0,
        flops.mean_decode_context(traffic.sizes(mix)))
    assert got == pytest.approx(100 * want / 819e9 / 0.012)
    assert 60 < got < 72  # ~8 ms of need over a 12 ms step
    # a family without the functions (a parent commit's, another family):
    # nothing to read, and no raise
    other = ctx_of(config, mix, family="llama")
    for name in NEW_METRICS[:3]:
        assert read_metric(name, other) is None
    # no trace, no spans: nothing
    bare = types.SimpleNamespace(**dict(vars(ctx), trace=None, host_spans=[]))
    assert read_metric("prefill_ms.serve_rate", bare) is None


def test_the_cell_lists_itself_where_its_metrics_are_true():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "mixed_closed64", "mimo_v25_l7_ep16")
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert judged == {"serve_tokens_per_s"}
    layer = {m["name"]: m for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert set(NEW_METRICS) | {"mfu.serve", "decode_step_ms.serve",
                               "replica_ready_s.serve"} <= set(layer)
    assert all(m["moves"] in judged | {"setup_s"} for m in layer.values())
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW_METRICS
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "serve_tokens_per_s"
    # the Nemotron cell's list, but for its own three
    nemotron = {m["name"] for m in bench["per_layer"]
                if "nemotron3s_ep4_agent_closed64" in m.get("workloads", [])}
    assert set(layer) - set(NEW_METRICS) == nemotron - {
        "hybrid_decode_roofline.serve", "latent_expert_tokens.serve",
        "experts_touched_pct.serve"}
