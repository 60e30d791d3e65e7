"""What PR 56 added to the yardstick, pinned on the CPU: the Olmo-Hybrid
configuration and traffic files, ``lib/flops_olmo_hybrid.py``'s arithmetic,
and the two new metric files (on readers that were there) on hand-built
spans.  Pure functions and files: no device, no timing.
"""

import importlib
import json
import os
import types

import pytest

from benchmarks.lib import flops, flops_olmo_hybrid as fl
from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "olmohybrid_l12_reason_closed64"
# What may differ from the source: the cut, and nothing that is a width.
REDUCED = ["num_hidden_layers"]
NEW_METRICS = ["delta_decode_roofline.serve", "delta_chunk_fill_pct.serve"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(HERE, "configs", "olmo_hybrid7b_l12.json")


@pytest.fixture(scope="module")
def mix():
    return load(HERE, "traffic", "reason_closed64.json")


def test_the_configuration_is_the_source_but_for_its_depth(config):
    published = config["published"]
    assert config["reduced"] == REDUCED
    assert {k for k in published if config[k] != published[k]} == set(REDUCED)
    assert (config["num_hidden_layers"], published["num_hidden_layers"]) == (
        12, 32)
    if os.path.exists(CATALOG):  # the catalog's row, key by key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert row["config"] == published
        assert row["source_url"] == config["source"]
    for key in ("assumed", "deployment", "memory", "reduced_why"):
        assert config[key]
    assert "pipeline" in config["deployment"]
    assert "layers 3-14" in config["reduced_why"]
    # what the config leaves open, each numbered item with its alternative
    # or its reason; the served length; the draw
    assert set(config["assumed"]) >= {
        "rope", "block_wiring", "qk_norm", "linear_attention", "projections",
        "chunk", "precision", "max_seq", "head", "weights"}
    for n, key in enumerate(("rope", "block_wiring", "qk_norm",
                             "linear_attention", "projections", "chunk",
                             "precision"), 1):
        assert config["assumed"][key].startswith(f"({n})")
    # The program's config at the published widths, key by key.
    m = config["model"]
    same = {"vocab_size": "vocab_size", "d_model": "hidden_size",
            "n_head": "num_attention_heads", "d_ff": "intermediate_size",
            "linear_num_heads": "linear_num_key_heads",
            "linear_key_head_dim": "linear_key_head_dim",
            "linear_value_head_dim": "linear_value_head_dim",
            "conv_kernel": "linear_conv_kernel_dim",
            "allow_neg_eigval": "linear_allow_neg_eigval",
            "rms_eps": "rms_norm_eps"}
    assert {k: m[k] for k in same} == {k: published[v]
                                       for k, v in same.items()}
    assert (published["num_key_value_heads"], published["hidden_size"]) == (
        m["n_head"], m["n_head"] * m["head_dim"])
    assert published["linear_num_value_heads"] == m["linear_num_heads"]
    assert published["rope_parameters"] == {"rope_theta": None}
    assert (published["tie_word_embeddings"], published["attention_bias"],
            published["hidden_act"]) == (False, False, "silu")
    # the twelve layers held here: layers 3-14 of the published list, three
    # whole periods in the published 3 : 1, a full layer first
    letters = {"linear_attention": "L", "full_attention": "F"}
    assert len(published["layer_types"]) == 32
    assert m["layer_pattern"] == "".join(
        letters[t] for t in published["layer_types"][3:15]) == "FLLL" * 3
    assert m["n_layer"] == len(m["layer_pattern"]) == 12
    assert config["engine"] == {"max_batch_size": 64, "max_seq_len": 2048}
    fam = importlib.import_module("benchmarks.families." + config["family"])
    for name in ("model", "tiny"):
        cfg = fam.config(config[name])
        # the harness's two-layer cut sees one layer of EACH kind
        assert cfg.kinds[:2] == "FL" and set(cfg.kinds) == set("FL")
        assert cfg.state_pack == 2
    # the harness's 64 + 3 positions are two chunks of the cell's and eight
    # of the rehearsal's: its prefill carries a state across a chunk
    assert m["chunk_size"] * 2 == config["tiny"]["chunk_size"] * 8 == 64
    bench = load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/olmo_hybrid7b_l12.json"
    assert len(entry["why"]) <= 200


def test_the_traffic_is_a_wide_batch_of_long_answers(config, mix):
    sizes = traffic.sizes(mix)
    eng = config["engine"]
    assert mix["kind"] == "serve_stream" and mix["temperature"] == 0.0
    assert mix["route"] == "/v1/completions"
    assert len(sizes) == mix["arrivals"]["clients"] == eng["max_batch_size"]
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 32,
         "max": 512},
        {"dist": "lognormal", "median": 768, "sigma": 0.5, "min": 256,
         "max": 1408})
    agent = load(HERE, "traffic", "agent_closed64.json")
    assert mix["prompt_tokens"] == agent["prompt_tokens"]
    assert max(p + o for p, o in sizes) == 1514 < eng["max_seq_len"] - 1
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            == 1920 < eng["max_seq_len"] - 1)
    rungs = [sum(1 for p, _ in sizes if lo < p <= hi) for lo, hi in (
        (0, 256), (256, 512), (512, 2048))]
    assert rungs == [56, 8, 0]
    assert sum(o for _, o in sizes) == 53379
    assert max(o for _, o in sizes) == 1408 > 1024  # no other cell's pass
    assert 615 < flops.mean_decode_context(sizes) < 630
    tiny = dict(mix, **mix["tiny"])
    assert max(p + o for p, o in traffic.sizes(tiny)) < (
        config["tiny_engine"]["max_seq_len"] - 1)
    a, b = traffic.requests(mix, 5600000019), traffic.requests(mix, 7)
    assert a != b and sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)


def test_the_parameter_count_is_the_published_models(config):
    """ISSUE 56's count: a linear layer's mixer 88.7 M (66.36 M in ``Wq | Wk |
    Wv | Wg``, 22.12 M ``Wo``, 0.23 M in the two scalars a head), a full
    layer's 58.98 M, the MLP 126.81 M; the whole model 7.4 B (described as
    "7B")."""
    m = config["model"]
    assert fl.mlp_params(m) == 3 * 3840 * 11008
    assert round(fl.mlp_params(m) / 1e6, 2) == 126.81
    assert fl.delta_params(m) == 3840 * (11520 + 5760 + 60) + 5760 * 3840
    assert round(3840 * (11520 + 5760) / 1e6, 2) == 66.36
    assert round(fl.delta_params(m) / 1e6, 2) == 88.70
    assert round(fl.attention_params(m) / 1e6, 2) == 58.98
    assert round(fl.layer_params(m, "L") / 1e6, 1) == 215.5
    assert round(fl.layer_params(m, "F") / 1e6, 1) == 185.8
    pub = dict(m, layer_pattern="LLLF" * 8, n_layer=32)
    whole = sum(fl.layer_params(pub, k) for k in fl.kinds(pub)) + (
        2 * 100352 * 3840)
    assert round(whole / 1e9, 2) == 7.43
    # this chip: 4.99 GB of layers, 0.77 of head; the embedding is gathered
    assert round(fl.weight_bytes(m) / 1e9, 2) == 5.76
    assert round((fl.weight_bytes(m) + 2 * 100352 * 3840) / 1e9, 2) == 6.54


def test_a_steps_bytes_and_a_prefills_operations_from_the_cells_shapes(
    config, mix
):
    m = config["model"]
    # a slot's state: 9 layers x (30 x 96 x 192 + 3 x 11520) float32
    assert fl.state_bytes_per_slot(m) == 9 * 4 * (552960 + 34560)
    assert round(fl.state_bytes_per_slot(m) / 1e6, 2) == 21.15
    assert fl.kv_bytes_per_token(m) == 3 * 15360 == 46080
    step = fl.decode_step_bytes(m, {}, 62.0, 622.0)
    assert step == pytest.approx(
        fl.weight_bytes(m) + 62 * (2 * fl.state_bytes_per_slot(m)
                                   + 622 * 46080))
    # 5.76 + 2.62 of state + 1.78 of live keys and values = 10.2 GB: 12.4 ms
    assert 12.0 < step / 819e9 * 1e3 < 12.8
    assert fl.decode_step_bytes(m, {}, 0, 0.0) == fl.weight_bytes(m)
    dense = 9 * fl.layer_params(m, "L") + 3 * fl.layer_params(m, "F")
    assert fl.decode_flops_per_token(m, 622.0) == pytest.approx(
        2 * (dense + 100352 * 3840) + 3 * 4 * 622 * 3840
        + 9 * 7 * 2880 * 192)
    # a prompt of 150 tokens: the products are nearly all of it (0.76
    # TFLOP), the chunked rule 0.7 %, the full layers' triangle less
    got = fl.prefill_flops(m, 150)
    rule = 9 * fl.delta_chunk_flops(m, 150, m["chunk_size"])
    assert got == pytest.approx(
        2 * dense * 150 + 3 * 4 * 128 * 30 * 150 * 151 / 2 + rule
        + 2 * 100352 * 3840)
    assert fl.delta_chunk_flops(m, 1, 64) == 30 * (
        2 * 64 * 96 + 64 * 288 + 6 * 96 * 192 + 64 * 192)
    assert 0.006 < rule / got < 0.008 and 0.75e12 < got < 0.77e12
    fam = importlib.import_module("benchmarks.families." + config["family"])
    assert fam.prefill_flops is fl.prefill_flops
    assert fam.decode_step_bytes is fl.decode_step_bytes
    assert fam.decode_flops_per_token is fl.decode_flops_per_token
    assert not hasattr(fam, "held_expert_slots")  # nothing is routed


# Two decode steps of a full batch as the engine writes its counts (one step
# late) on zero-length spans; the second folded a prefill of 150 tokens at
# the 256 rung; one decode program of 24 ms.
COUNTS = [
    {"occupied": 64, "waiting": 0, "admitted": 0, "retired": 0,
     "host_syncs": 1, "delta_positions": 64, "delta_chunk_positions": 64},
    {"occupied": 62, "waiting": 0, "admitted": 1, "retired": 1,
     "host_syncs": 2, "delta_positions": 62, "delta_chunk_positions": 64,
     "prefill_delta_positions": 150, "prefill_delta_chunk_positions": 256,
     "trace_id": "abc"},
]
MS = 1_000_000
HOST = [[["engine.step", 0, 100, {"seq": 0}],
         ["engine.counts", 90, 0, COUNTS[0]],
         ["engine.step", 200, 100, {"seq": 1}],
         ["engine.counts", 290, 0, COUNTS[1]]]]
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 50]],
    tr.MODULES_LINE: [["jit__lambda(1)", 0, 24 * MS],
                      ["jit__lambda(2)", 320 * MS, 24 * MS]]}}


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
    assert read_metric("delta_chunk_fill_pct.serve", ctx) == pytest.approx(
        100 * 150 / 256)  # the one prefill's; a decode step's are not read
    got = read_metric("delta_decode_roofline.serve", ctx)
    want = fl.decode_step_bytes(
        config["model"], {}, 63.0,
        flops.mean_decode_context(traffic.sizes(mix)))
    assert got == pytest.approx(100 * want / 819e9 / 0.024)
    assert 45 < got < 60  # ~12.5 ms of need over a 24 ms step
    mfu = read_metric("mfu.serve", ctx)  # the accepted share of the peak
    assert mfu == pytest.approx(100 * 63 * fl.decode_flops_per_token(
        config["model"], flops.mean_decode_context(traffic.sizes(mix)))
        / 0.024 / 197e12)
    assert 0 < mfu < 105
    # a family without the functions or the counts (a parent commit's):
    # nothing, no raise
    other = ctx_of(config, mix, family="llama")
    assert read_metric("delta_decode_roofline.serve", other) is None
    bare = types.SimpleNamespace(**dict(
        vars(ctx), trace=None, host_spans=[]))
    for name in NEW_METRICS:
        assert read_metric(name, bare) is None
    uncounted = types.SimpleNamespace(**dict(vars(ctx), host_spans=[
        hs.from_planes([[["engine.counts", 90, 0, {"occupied": 64}]]],
                       DEVICE)]))
    assert read_metric("delta_chunk_fill_pct.serve", uncounted) is None


def test_the_cell_lists_itself_where_its_metrics_are_true():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "reason_closed64", "olmo_hybrid7b_l12")
    assert len(cell["why"]) <= 200
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert judged == {"serve_tokens_per_s"}  # 64 streams share the replica's
    # threads: the percentiles stay in the notes, as in the Nemotron cell
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
    # the Laguna cell's list as PR 56 found it, but for what reads an expert
    # layer (by name, so that a metric a later PR gives either cell breaks
    # nothing here)
    assert set(layer) >= {
        "replica_ready_s.serve", "decode_step_ms.serve",
        "device_idle_pct.serve", "decode_step_host_ms.serve",
        "idle_in_sample_pct.serve", "idle_outside_step_pct.serve",
        "queue_wait_ms.serve", "occupied_slots_mean.serve",
        "host_syncs_per_step.serve", "mfu.serve", "chunks_per_write.serve",
        "tokens_per_delta.serve", "replica_spawn_s.serve",
        "engine_build_s.serve", "xla_compile_s.serve",
        "prefill_ms.serve_rate", "cache_read_pct.serve", "prefill_mfu.serve",
        "prefill_useful_pct.serve_rate", *NEW_METRICS}
    # every share of a peak that moves what the cell reports is reported
    assert {"mfu.serve", "prefill_mfu.serve"} <= set(layer)
    # one four-chip cell, as before (nothing here pins what a later PR
    # appends: the cell's own list is compared by name)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
