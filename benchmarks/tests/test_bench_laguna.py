"""What PR 52 added to the yardstick, pinned on the CPU: the Laguna
configuration and traffic files, ``lib/flops_laguna.py``'s arithmetic, and the
three new metric files (on readers that were there) on hand-built spans.
Pure functions and files: no device, no timing.
"""

import importlib
import json
import os
import types

import pytest

from benchmarks.lib import flops, flops_laguna as fl
from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "laguna_ep16_code_closed32"
# What may differ from the source: the cuts, and nothing that is a width.
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ["ring_long_decode_roofline.serve", "top10_expert_tokens.serve",
               "top10_experts_touched_pct.serve"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(HERE, "configs", "laguna_s21_l9_ep16.json")


@pytest.fixture(scope="module")
def mix():
    return load(HERE, "traffic", "code_closed32.json")


def test_the_configuration_is_the_source_but_for_its_three_cuts(config):
    published = config["published"]
    assert config["reduced"] == REDUCED
    assert {k for k in published if config[k] != published[k]} == set(REDUCED)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (9, 16, 12544)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    if os.path.exists(CATALOG):  # the catalog's row, key by key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-S-2.1")
        assert row["config"] == published
        assert row["source_url"] == config["source"]
    for key in ("assumed", "deployment", "memory", "reduced_why"):
        assert config[key]
    assert "16 chips" in config["deployment"]
    assert "layers 0-8" in config["deployment"]
    # the five pointwise choices the config leaves open, each with its
    # alternative; the served length; the draw
    assert set(config["assumed"]) >= {"qk_norm", "gate", "router",
                                      "shared_expert", "rope", "max_seq",
                                      "weights"}
    for n, key in enumerate(("qk_norm", "gate", "router", "shared_expert",
                             "rope"), 1):
        assert config["assumed"][key].startswith(f"({n})")
        assert "Alternative" in config["assumed"][key]
    # The program's config at the published widths, key by key.
    m = config["model"]
    same = {"d_model": "hidden_size", "n_head": "num_attention_heads",
            "n_kv_head": "num_key_value_heads", "head_dim": "head_dim",
            "window": "sliding_window", "d_ff": "intermediate_size",
            "d_expert": "moe_intermediate_size",
            "n_routed_experts": "num_experts", "top_k": "num_experts_per_tok",
            "routed_scaling_factor": "moe_routed_scaling_factor",
            "rms_eps": "rms_norm_eps"}
    assert {k: m[k] for k in same} == {k: published[v]
                                       for k, v in same.items()}
    assert m["d_expert"] == published["shared_expert_intermediate_size"]
    full = published["rope_parameters"]["full_attention"]
    same = {"rope_theta": "rope_theta", "rope_factor": "factor",
            "rope_original_max": "original_max_position_embeddings",
            "rope_beta_fast": "beta_fast", "rope_beta_slow": "beta_slow",
            "rope_attention_factor": "attention_factor"}
    assert {k: m[k] for k in same} == {k: full[v] for k, v in same.items()}
    assert m["rotary_dim"] == m["head_dim"] * full["partial_rotary_factor"]
    sliding = published["rope_parameters"]["sliding_attention"]
    assert (m["rope_theta_window"], sliding["partial_rotary_factor"],
            sliding["rope_type"]) == (sliding["rope_theta"], 1, "default")
    # heads by layer kind, and the kinds of the nine layers held here
    heads = {"F": m["n_head"], "W": m["n_head_window"]}
    letters = {"full_attention": "F", "sliding_attention": "W",
               "dense": "D", "sparse": "E"}
    assert m["attn_pattern"] == "".join(
        letters[t] for t in published["layer_types"][:9]) == "FWWWFWWWF"
    assert m["mlp_pattern"] == "".join(
        letters[t] for t in published["mlp_layer_types"][:9]) == "DEEEEEEEE"
    assert [heads[k] for k in m["attn_pattern"]] == (
        published["num_attention_heads_per_layer"][:9])
    assert (published["mlp_only_layers"], published["norm_topk_prob"],
            published["moe_router_logit_softcapping"],
            published["moe_apply_router_weight_on_input"],
            published["tie_word_embeddings"], published["gating"]) == (
                [0], True, 0, False, False, "per-head")
    assert (m["n_layer"], m["experts_held"], m["vocab_size"]) == (9, 16, 12544)
    assert config["engine"] == {"max_batch_size": 32, "max_seq_len": 16384}
    fam = importlib.import_module("benchmarks.families." + config["family"])
    for name in ("model", "tiny"):
        cfg = fam.config(config[name])
        assert cfg.experts_held * 2 <= cfg.n_routed_experts
        assert (cfg.n_head // cfg.n_kv_head,
                cfg.n_head_window // cfg.n_kv_head) == (6, 9)
        assert set(cfg.attn_kinds) == set("FW")
    # the rehearsal's 64 + 3 positions wrap the tiny ring eight times and
    # cross the tiny trained length four times; the chip's two-layer check
    # stays inside the published window
    tiny = config["tiny"]
    assert tiny["window"] * 8 <= 64 and tiny["rope_original_max"] * 4 <= 64
    assert 64 + 3 < m["window"] < m["rope_original_max"]
    bench = load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/laguna_s21_l9_ep16.json"


def test_the_traffic_fits_the_cell_and_falls_on_the_two_long_rungs(config,
                                                                   mix):
    sizes = traffic.sizes(mix)
    eng = config["engine"]
    assert mix["kind"] == "serve_stream" and mix["temperature"] == 0.0
    assert len(sizes) == mix["arrivals"]["clients"] == eng["max_batch_size"]
    assert max(p + o for p, o in sizes) == 14745 < eng["max_seq_len"] - 1
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            == 15104 < eng["max_seq_len"] - 1)
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 8192, "sigma": 0.5, "min": 2048,
         "max": 14336},
        {"dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64,
         "max": 768})  # longdoc_closed32's: ISSUE 52's fallback, taken
    rungs = [sum(1 for p, _ in sizes if lo < p <= hi) for lo, hi in (
        (0, 4096), (4096, 8192), (8192, 16384))]
    assert rungs == [0, 17, 15]
    # every prompt wraps its rings at least nine times; a round of the 32
    # is 9,207 output tokens
    assert min(p for p, _ in sizes) == 4745 > 9 * config["model"]["window"]
    assert sum(o for _, o in sizes) == 9207
    assert 8850 < flops.mean_decode_context(sizes) < 8950
    tiny = dict(mix, **mix["tiny"])
    assert max(p + o for p, o in traffic.sizes(tiny)) < (
        config["tiny_engine"]["max_seq_len"] - 1)
    a, b = traffic.requests(mix, 5200000019), traffic.requests(mix, 7)
    assert a != b and sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)


def test_the_parameter_count_is_the_published_models(config):
    """ISSUE 52's count: a full layer's attention 44.19 M, a sliding one's
    63.14 M, the shared or one routed expert 9.437 M, a router 0.786 M, the
    dense MLP 113.25 M; the whole model 117.56 B (described as "118B"), 8.45
    B a token."""
    pub = dict(config["model"], attn_pattern="FWWW" * 12,
               mlp_pattern="D" + "E" * 47, n_layer=48, experts_held=256,
               vocab_size=config["published"]["vocab_size"])
    assert fl.attention_params(pub, "F") == (
        2 * 3072 * 48 * 128 + 2 * 3072 * 8 * 128 + 3072 * 48)
    assert round(fl.attention_params(pub, "F") / 1e6, 2) == 44.19
    assert round(fl.attention_params(pub, "W") / 1e6, 2) == 63.14
    assert fl.expert_params(pub) == 3 * 3072 * 1024 == 9437184
    assert round(fl.router_params(pub) / 1e6, 3) == 0.786
    assert round(fl.dense_mlp_params(pub) / 1e6, 2) == 113.25
    assert fl.model_params(pub, 256) == (
        12 * fl.attention_params(pub, "F") + 36 * fl.attention_params(pub, "W")
        + fl.dense_mlp_params(pub) + 47 * (786432 + 9437184)
        + 47 * 256 * 9437184 + 2 * 100352 * 3072)
    assert round(fl.model_params(pub, 256) / 1e9, 2) == 117.56
    assert round(fl.model_params(pub, 10) / 1e9, 2) == 8.45
    # the driver's "about 54 M a layer" counts 48 heads in every layer
    mean = (12 * fl.attention_params(pub, "F")
            + 36 * fl.attention_params(pub, "W")) / 48 + 786432 + 9437184
    assert round(mean / 1e6, 1) == 68.6  # + the routed experts' 2,416 M


def test_a_steps_bytes_and_a_prefills_operations_from_the_cells_shapes(
    config
):
    m = config["model"]
    assert fl.held_expert_slots(m) == 16 * 8 == 128
    # 3 + 6 attentions, the dense layer, 8 shared experts in bf16, eight
    # float32 routers, the head: 1.50 GB (ISSUE 52's 1.43 + 0.08)
    assert round(fl.nonexpert_weight_bytes(m) / 1e9, 2) == 1.50
    assert fl.kv_bytes_per_position(m) == 4096
    # a slot at 9,000 positions: three full layers at 9,000 + six rings of 512
    assert fl.positions_seen(m, 9000.0) == 3 * 9000 + 6 * 512
    assert fl.positions_seen(m, 300.0) == 9 * 300  # inside the window
    step = fl.decode_step_bytes(m, {"experts_touched": 92.0}, 31.6, 9000.0)
    assert step == pytest.approx(
        fl.nonexpert_weight_bytes(m) + 92 * 2 * 9437184
        + 31.6 * (27000 + 3072) * 4096)
    assert 8.5 < step / 819e9 * 1e3 < 9.0  # ms at the v5e's bandwidth
    # idle: no slot, no expert: the weights outside the experts alone
    assert fl.decode_step_bytes(m, {"experts_touched": 0.0}, 0, 0.0) == (
        fl.nonexpert_weight_bytes(m))
    per_token = 2 * (fl.nonexpert_params(m) + 8 * 9437184 * 10 / 16)
    assert fl.decode_flops_per_token(m, 9000.0) == pytest.approx(
        per_token + 2 * 12544 * 3072
        + 2 * 256 * (3 * 48 * 9000 + 6 * 72 * 512))
    # whole rungs: ISSUE 52 reckoned 7.7 / 16.7 / 38.3 TFLOP with the band
    # AS COMPUTED (two tiles of 512 a query tile: 0.9 / 1.9 / 3.7); counted
    # as useful work (below the diagonal, inside the window: half of that)
    # they are 7.2 / 15.7 / 36.4
    for tokens, tflop, as_computed in ((4096, 7.2, 7.7), (8192, 15.7, 16.7),
                                       (16384, 36.4, 38.3)):
        got = fl.prefill_flops(m, tokens)
        band = 512 * 513 / 2 + (tokens - 512) * 512
        assert got == pytest.approx(
            per_token * tokens + 2 * 12544 * 3072
            + 512 * (3 * 48 * tokens * (tokens + 1) / 2 + 6 * 72 * band))
        assert round(got / 1e12, 1) == tflop
        two_tiles = 6 * 72 * 512 * (tokens * 1024 - band)
        assert round((got + two_tiles) / 1e12, 1) == pytest.approx(
            as_computed, abs=0.11)
    assert fl.pairs_seen(300, 512) == fl.pairs_seen(300) == 300 * 301 / 2
    fam = importlib.import_module("benchmarks.families." + config["family"])
    assert fam.prefill_flops is fl.prefill_flops
    assert fam.decode_step_bytes is fl.decode_step_bytes
    assert fam.held_expert_slots is fl.held_expert_slots
    assert fam.decode_flops_per_token is fl.decode_flops_per_token


# Two decode steps of a full batch as the engine writes its counts (one step
# late) on zero-length spans, one decode program of 14 ms.
COUNTS = [
    {"occupied": 32, "waiting": 0, "admitted": 0, "retired": 0,
     "host_syncs": 1, "routed_total": 2560, "routed_held": 166,
     "experts_touched": 94},
    {"occupied": 30, "waiting": 0, "admitted": 1, "retired": 1,
     "host_syncs": 2, "routed_total": 2400, "routed_held": 146,
     "experts_touched": 88, "trace_id": "abc"},
]
MS = 1_000_000
HOST = [[["engine.step", 0, 100, {"seq": 0}],
         ["engine.counts", 90, 0, COUNTS[0]],
         ["engine.step", 200, 100, {"seq": 1}],
         ["engine.counts", 290, 0, COUNTS[1]]]]
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 50]],
    tr.MODULES_LINE: [["jit__lambda(1)", 0, 14 * MS],
                      ["jit__lambda(2)", 320 * MS, 14 * MS]]}}


def ctx_of(config, mix, family=None):
    return types.SimpleNamespace(
        host_spans=[hs.from_planes(HOST, DEVICE)],
        trace=tr.Trace.from_planes(DEVICE),
        config=dict(config, family=family or config["family"]), mix=mix,
        stats={"model": config["model"]},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def read_metric(name, ctx):
    """A metric file's reader on its own arguments, as ``run.py`` calls it."""
    spec = load(HERE, "layer_metrics", name + ".json")
    assert spec["name"] == name and spec["what"]
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    return reader.read(ctx, **spec["args"])


def test_the_new_metric_files_read_hand_built_spans(config, mix):
    ctx = ctx_of(config, mix)
    assert read_metric("top10_expert_tokens.serve", ctx) == pytest.approx(
        (166 + 146) / 2 / 128)  # 1.22
    assert read_metric(
        "top10_experts_touched_pct.serve", ctx) == pytest.approx(
            100 * 91 / 128)  # 71 %
    got = read_metric("ring_long_decode_roofline.serve", ctx)
    want = fl.decode_step_bytes(
        config["model"], {"experts_touched": 91.0}, 31.0,
        flops.mean_decode_context(traffic.sizes(mix)))
    assert got == pytest.approx(100 * want / 819e9 / 0.014)
    assert 55 < got < 70  # ~8.7 ms of need over a 14 ms step
    # a family without the functions (a parent commit's): nothing, no raise
    other = ctx_of(config, mix, family="llama")
    for name in NEW_METRICS:
        assert read_metric(name, other) is None
    bare = types.SimpleNamespace(**dict(
        vars(ctx), trace=None, host_spans=[]))
    for name in NEW_METRICS:
        assert read_metric(name, bare) is None


def test_the_cell_lists_itself_where_its_metrics_are_true():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "code_closed32", "laguna_s21_l9_ep16")
    assert len(cell["why"]) <= 200
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert judged == {"serve_tokens_per_s"}  # a token gap here is a
    # neighbour's whole prefill: the percentiles stay in the notes
    layer = {m["name"]: m for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert all(m["moves"] in judged | {"setup_s"} for m in layer.values())
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "serve_tokens_per_s"
        assert layer[name]["layer"] == "model step"
        spec = load(HERE, "layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(
            HERE, "readers", spec["reader"] + ".py"))
    # the Mistral-4 cell's list, but for each cell's own three
    mistral4 = {m["name"] for m in bench["per_layer"]
                if "mistral4_ep8_longdoc_closed32" in m.get("workloads", [])}
    assert set(layer) - set(NEW_METRICS) == mistral4 - {
        "latent_long_decode_roofline.serve", "ep8_expert_tokens.serve",
        "ep8_experts_touched_pct.serve"}
    # every share of a peak that moves what the cell reports is reported
    assert {"mfu.serve", "prefill_mfu.serve"} <= set(layer)
    # one four-chip cell, as before
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
