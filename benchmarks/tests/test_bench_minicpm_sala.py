"""What PR 62 added to the yardstick, pinned on the CPU: the MiniCPM-SALA
configuration and traffic files, ``lib/flops_minicpm_sala.py``'s arithmetic,
and the eight new metric files (on readers that were there) on hand-built
spans.  Pure functions and files: no device, no timing.
"""

import importlib
import json
import os
import re
import statistics
import types

import pytest

from benchmarks.lib import flops, flops_granite_h, flops_minicpm_sala as fl
from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "sala_l12_longctx_closed8"
NEW_METRICS = ["lightning_ms.serve", "select_ms.serve",
               "prefill_lightning_ms.serve_rate",
               "prefill_sparse_ms.serve_rate", "sparse_read_pct.serve",
               "lightning_chunk_fill_pct.serve", "sala_decode_roofline.serve",
               "sala_unscoped_pct.serve"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(HERE, "configs", "minicpm_sala_l12.json")


@pytest.fixture(scope="module")
def mix():
    return load(HERE, "traffic", "longctx_closed8.json")


def test_the_configuration_is_the_source_cut_in_depth_alone(config):
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert {k for k, v in published.items() if config[k] != v} == {
        "num_hidden_layers"}
    assert (published["num_hidden_layers"], config["num_hidden_layers"]) == (
        32, 12)
    if os.path.exists(CATALOG):  # the catalog's row, key by key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiniCPM-SALA")
        assert row["config"] == published
        assert row["source_url"] == config["source"]
    m = config["model"]
    # the cut is the published layers 9-20, letter for letter
    kinds = "".join("S" if t == "minicpm4" else "L"
                    for t in published["mixer_types"])
    assert kinds.count("S") == 8 and kinds.count("L") == 24
    first = m["first_layer"]
    assert m["layer_pattern"] == kinds[first:first + 12] == "SLLLLLLSSLLL"
    assert m["n_layer"] == 12 and m["published_layers"] == 32
    # every width as published
    assert (m["d_model"], m["d_ff"], m["vocab_size"]) == (
        published["hidden_size"], published["intermediate_size"],
        published["vocab_size"])
    assert (m["n_head"], m["n_kv_head"], m["head_dim"]) == (
        published["num_attention_heads"], published["num_key_value_heads"],
        published["head_dim"])
    assert (m["lightning_heads"], m["lightning_head_dim"]) == (
        published["lightning_nh"], published["lightning_head_dim"])
    assert published["lightning_nkv"] == m["lightning_heads"]
    assert (m["scale_emb"], m["scale_depth"], m["dim_model_base"],
            m["rope_theta"], m["rms_eps"]) == (
        published["scale_emb"], published["scale_depth"],
        published["dim_model_base"], published["rope_theta"],
        published["rms_norm_eps"])
    # MiniCPM4's sparse_config
    assert [m[k] for k in ("kernel_size", "kernel_stride", "block_size",
                           "topk", "init_blocks", "window_size",
                           "dense_len")] == [32, 16, 64, 64, 1, 2048, 8192]
    for key in ("assumed", "deployment", "memory", "reduced_why"):
        assert config[key]
    assert "three stages" in config["deployment"]
    assert "2.7 x" in config["deployment"]
    assert set(config["assumed"]) >= {
        "slopes", "sparse_config", "unread", "norms", "gates", "rotary",
        "precision", "chunk", "max_seq", "weights"}
    assert "mup_denominator" in config["assumed"]["unread"]
    assert config["engine"] == {"max_batch_size": 8, "max_seq_len": 16384}
    tiny = config["tiny"]
    assert tiny["layer_pattern"] == "SLLSSL" and tiny["dense_len"] == 64
    bench = load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/minicpm_sala_l12.json"
    assert len(entry["why"]) <= 200
    names = [c["name"] for c in bench["configs"]]
    assert names.index(config["name"]) == names.index("granite4h_micro") + 1


def test_the_traffic_is_long_documents_all_past_dense_len(config, mix):
    sizes = traffic.sizes(mix)
    eng, m = config["engine"], config["model"]
    assert mix["kind"] == "serve_stream" and mix["temperature"] == 0.0
    assert mix["route"] == "/v1/completions"
    assert mix["arrivals"] == {"kind": "closed", "clients": 8}
    assert mix["arrivals"]["clients"] == eng["max_batch_size"]
    assert len(sizes) == mix["population"] == 32
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 12288, "sigma": 0.3, "min": 8448,
         "max": 15104},
        # ISSUE 62's named fallback: the file's ``population_why``
        {"dist": "lognormal", "median": 768, "sigma": 0.2, "min": 512,
         "max": 1024})
    # EVERY prompt past dense_len, all at the top rung
    assert min(p for p, _ in sizes) == 8448 > m["dense_len"]
    assert all(8192 < p <= 16384 for p, _ in sizes)
    assert max(p + o for p, o in sizes) == 16128 < eng["max_seq_len"] - 1
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            == 16128 < eng["max_seq_len"] - 1)
    assert sum(o for _, o in sizes) == 25054
    assert 12700 < flops.mean_decode_context(sizes) < 12900
    tiny = dict(mix, **mix["tiny"])
    tiny_sizes = traffic.sizes(tiny)
    assert min(p for p, _ in tiny_sizes) >= 72 > config["tiny"]["dense_len"]
    assert max(p + o for p, o in tiny_sizes) < (
        config["tiny_engine"]["max_seq_len"] - 1)
    a, b = traffic.requests(mix, 6200000019), traffic.requests(mix, 7)
    assert a != b and sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)


def test_the_population_seed_follows_chat_closed16s_rule(mix):
    """Of seeds 0..399 the draw whose medians and means sit closest to the
    distribution's own (the means after clipping, from a large draw); the
    same seed for the outputs as ISSUE 62 issued them (sigma 0.35 in
    256-1024), of which this file holds the named fallback."""
    import random

    def own_mean(spec):
        rng = random.Random(12345)
        return statistics.fmean(traffic._draw(spec, rng)
                                for _ in range(100000))

    def ranked(mix):
        means = [own_mean(mix[k]) for k in ("prompt_tokens", "output_tokens")]

        def deviation(seed):
            sizes = traffic.sizes(dict(mix, population_seed=seed))
            total = 0.0
            for col, key, mean in zip(zip(*sizes), (
                    "prompt_tokens", "output_tokens"), means):
                total += abs(statistics.median(col) - mix[key]["median"]
                             ) / mix[key]["median"] + abs(
                                 statistics.fmean(col) - mean) / mean
            return total

        order = sorted(range(400), key=deviation)
        return means, order, deviation

    means, order, deviation = ranked(mix)
    assert 12150 < means[0] < 12250 and 774 < means[1] < 780
    assert order[0] == mix["population_seed"] == 367
    assert deviation(367) < 0.015 < 0.022 < deviation(order[1])
    issued = dict(mix, output_tokens={
        "dist": "lognormal", "median": 768, "sigma": 0.35, "min": 256,
        "max": 1024})
    assert ranked(issued)[1][0] == 367


def test_the_parameter_count_is_the_published_models(config):
    """ISSUE 62's count: a lightning layer 83.9 M in its mixer, a sparse
    layer 52.4 M, an MLP 201.3 M; the published 32 layers and two tables
    9.48 B; the cut 3,328 M of layers and 601.7 M of tables, 7.86 GB in
    bfloat16."""
    m = config["model"]
    assert fl.lightning_params(m) == 5 * 4096 * 4096
    assert fl.sparse_params(m) == 3 * 4096 * 4096 + 2 * 4096 * 256
    assert round(fl.sparse_params(m) / 1e6, 1) == 52.4
    assert fl.mlp_params(m) == 3 * 4096 * 16384
    assert round(fl.layer_params(m, "L") / 1e6, 1) == 285.2
    assert round(fl.layer_params(m, "S") / 1e6, 1) == 253.8
    assert fl.table_params(m) == 73448 * 4096
    whole = dict(m, n_layer=32, layer_pattern="".join(
        "S" if t == "minicpm4" else "L"
        for t in config["published"]["mixer_types"]))
    assert round(fl.total_params(whole) / 1e9, 2) == 9.48
    assert round(fl.total_params(m) / 1e9, 2) == 3.93
    assert round(2 * fl.total_params(m) / 1e9, 2) == 7.86
    # a decode step reads the head, not the embedding's table
    assert round(fl.weight_bytes(m) / 1e9, 2) == 7.26


def test_a_steps_bytes_and_a_prefills_operations_from_the_cells_shapes(
    config, mix
):
    m = config["model"]
    # a slot's state: 9 layers x 32 heads x 128 x 128 float32
    assert fl.state_bytes_per_slot(m) == 9 * 4 * 32 * 128 * 128
    assert round(8 * fl.state_bytes_per_slot(m) / 1e9, 2) == 0.15
    # under dense_len everything, from there on 64 blocks of 64
    assert fl.read_positions(m, 5000.0) == 5000.0
    assert fl.read_positions(m, 8192.0) == fl.read_positions(m, 16000.0) == 4096
    row = 2 * 2 * 128  # a position's keys (or values) of one layer, bf16
    assert fl.sparse_bytes_per_slot(m, 5000.0) == 3 * row * 2 * 5000
    assert fl.sparse_bytes_per_slot(m, 12800.0) == 3 * row * (
        2 * 4096 + 12800 / 16)
    step = fl.decode_step_bytes(m, {}, 8.0, 12856.0)
    assert step == pytest.approx(fl.weight_bytes(m) + 8 * (
        2 * fl.state_bytes_per_slot(m)
        + fl.sparse_bytes_per_slot(m, 12856.0)))
    # 7.26 of weights + 0.30 of state both ways + 0.11 of listed blocks
    assert 9.3 < step / 819e9 * 1e3 < 9.5
    assert fl.decode_step_bytes(m, {}, 0, 0.0) == fl.weight_bytes(m)
    assert fl.decode_flops_per_token(m, 12856.0) == pytest.approx(
        2 * (fl.total_params(m) - fl.table_params(m))
        + 3 * 2 * 4096 * (2 * 4096 + 12856 / 16) + 9 * 5 * 32 * 128 * 128)
    # a prompt of 12,232 tokens: the products are nearly all of its 82 TFLOP
    got = fl.prefill_flops(m, 12232)
    scan = 9 * flops_granite_h.ssd_chunk_flops(fl.as_mamba(m), 12232, 256)
    dense = 9 * fl.layer_params(m, "L") + 3 * fl.layer_params(m, "S")
    read = 4096 * 4097 / 2 + (12232 - 4096) * 4096
    pooled = 12232 * 12233 / 2 / 16
    assert got == pytest.approx(
        2 * dense * 12232 + 3 * 2 * 4096 * (2 * read + pooled) + scan
        + 2 * 73448 * 4096)
    assert 8.0e13 < got < 8.5e13
    assert 0.005 < scan / got < 0.02
    # under dense_len: the triangle, no pooled scores
    short = fl.prefill_flops(m, 1000)
    assert short == pytest.approx(
        2 * dense * 1000 + 3 * 2 * 4096 * 2 * 1000 * 1001 / 2
        + 9 * flops_granite_h.ssd_chunk_flops(fl.as_mamba(m), 1000, 256)
        + 2 * 73448 * 4096)
    fam = importlib.import_module("benchmarks.families." + config["family"])
    assert fam.prefill_flops is fl.prefill_flops
    assert fam.decode_step_bytes is fl.decode_step_bytes
    assert fam.decode_flops_per_token is fl.decode_flops_per_token


# Two decode steps of a full batch as the engine writes its counts (one step
# late) on zero-length spans; the second folded a prefill of 12,000 tokens at
# the 16,384 rung; one decode program of 11 ms.
COUNTS = [
    {"occupied": 8, "waiting": 0, "admitted": 0, "retired": 0,
     "host_syncs": 1, "lightning_positions": 72,
     "lightning_chunk_positions": 72, "sparse_read_positions": 3 * 8 * 4096,
     "sparse_live_positions": 3 * 8 * 12000},
    {"occupied": 7, "waiting": 0, "admitted": 1, "retired": 1,
     "host_syncs": 2, "lightning_positions": 63,
     "lightning_chunk_positions": 72, "sparse_read_positions": 3 * 7 * 4096,
     "sparse_live_positions": 3 * 7 * 13000,
     "prefill_lightning_positions": 9 * 12000,
     "prefill_lightning_chunk_positions": 9 * 16384,
     "prefill_sparse_read_positions": 3 * 12000,
     "prefill_sparse_live_positions": 3 * 12000, "trace_id": "abc"},
]
MS = 1_000_000
HOST = [[["engine.step", 0, 100, {"seq": 0}],
         ["engine.counts", 90, 0, COUNTS[0]],
         ["engine.step", 200, 100, {"seq": 1}],
         ["engine.counts", 290, 0, COUNTS[1]]]]
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 50]],
    tr.MODULES_LINE: [["jit__lambda(1)", 0, 11 * MS],
                      ["jit__lambda(2)", 320 * MS, 11 * MS]]}}


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
    assert read_metric("lightning_chunk_fill_pct.serve", ctx) == (
        pytest.approx(100 * 12000 / 16384))  # the one prefill's
    assert read_metric("sparse_read_pct.serve", ctx) == pytest.approx(
        100 * (8 + 7) * 4096 / (8 * 12000 + 7 * 13000))  # decode steps'
    got = read_metric("sala_decode_roofline.serve", ctx)
    context = flops.mean_decode_context(traffic.sizes(mix))
    want = fl.decode_step_bytes(config["model"], {}, 7.5, context)
    assert got == pytest.approx(100 * want / 819e9 / 0.011)
    assert 80 < got < 90  # ~9.4 ms of need over an 11 ms step
    mfu = read_metric("mfu.serve", ctx)  # the accepted share of the peak
    assert mfu == pytest.approx(100 * 7.5 * fl.decode_flops_per_token(
        config["model"], context) / 0.011 / 197e12)
    assert 0 < mfu < 105
    # the scope readers' arguments; no table of operations here, so nothing
    # to read
    scopes = {name: load(HERE, "layer_metrics", name + ".json")
              for name in NEW_METRICS}
    for name, part, module in (
            ("lightning_ms.serve", "lightning", "^jit__lambda"),
            ("select_ms.serve", "select", "^jit__lambda"),
            ("prefill_lightning_ms.serve_rate", "lightning",
             "^jit_prefill_one"),
            ("prefill_sparse_ms.serve_rate", "attn", "^jit_prefill_one")):
        spec = scopes[name]
        assert spec["reader"] == "scope_ms_per_run"
        assert spec["args"]["per_module"] == module
        assert spec["args"]["stat"] == "ms"
        scope = re.compile(spec["args"]["scope"])
        assert scope.search(f"jit(f)/sala.{part}/while/body/dot")
        assert not scope.search("jit(f)/sala.mlp/dot_general")
        assert not scope.search(f"jit(f)/sala.{part}_like/dot")
        assert not scope.search(f"jit(f)/granite.{part}/dot") or part == "x"
        assert read_metric(name, ctx) is None
    outside = scopes["sala_unscoped_pct.serve"]["args"]
    assert outside["stat"] == "pct_outside"
    for part in ("embed", "lightning", "attn", "select", "mlp", "head"):
        assert re.search(outside["scope"], f"jit(f)/sala.{part}/x")
    assert not re.search(outside["scope"], "jit(f)/granite.mamba/x")
    # the selection lies INSIDE the attention's scope: attn_ms.serve, the
    # accepted metric the cell joins, counts it too
    attn = load(HERE, "layer_metrics", "attn_ms.serve.json")["args"]["scope"]
    assert re.search(attn, "jit(f)/sala.attn/sala.select/top_k")
    # a family without the functions or the counts (a parent commit's):
    # nothing, no raise
    other = ctx_of(config, mix, family="llama")
    assert read_metric("sala_decode_roofline.serve", other) is None
    bare = types.SimpleNamespace(**dict(
        vars(ctx), trace=None, host_spans=[]))
    for name in NEW_METRICS:
        assert read_metric(name, bare) is None
    uncounted = types.SimpleNamespace(**dict(vars(ctx), host_spans=[
        hs.from_planes([[["engine.counts", 90, 0, {"occupied": 8}]]],
                       DEVICE)]))
    assert read_metric("lightning_chunk_fill_pct.serve", uncounted) is None
    assert read_metric("sparse_read_pct.serve", uncounted) is None


def test_the_cell_lists_itself_where_its_metrics_are_true():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    names = [w["name"] for w in bench["workloads"]]
    # appended after the Granite cell (by name: a later cell breaks nothing)
    assert names.index(CELL) == names.index(
        "granite4h_micro_chat_closed64") + 1
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "longctx_closed8", "minicpm_sala_l12")
    assert len(cell["why"]) <= 200
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert judged == {"serve_tokens_per_s"}
    layer = {m["name"]: m for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert all(m["moves"] in judged | {"setup_s"} for m in layer.values())
    for name in NEW_METRICS:
        assert layer[name]["workloads"][0] == CELL
        assert layer[name]["moves"] == "serve_tokens_per_s"
        assert layer[name]["layer"] == "model step"
        assert layer[name]["unit"] in ("%", "ms")
        spec = load(HERE, "layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(
            HERE, "readers", spec["reader"] + ".py"))
    # the Granite cell's list but for what would misread this family: the
    # shared scope patterns know no ``lightning`` (state_ms, unscoped_pct),
    # and cache_read_pct's attributes say what a contiguous read takes
    assert set(layer) >= {
        "replica_ready_s.serve", "decode_step_ms.serve",
        "device_idle_pct.serve", "decode_step_host_ms.serve",
        "idle_in_sample_pct.serve", "idle_outside_step_pct.serve",
        "queue_wait_ms.serve", "occupied_slots_mean.serve",
        "host_syncs_per_step.serve", "mfu.serve", "chunks_per_write.serve",
        "tokens_per_delta.serve", "replica_spawn_s.serve",
        "engine_build_s.serve", "xla_compile_s.serve",
        "prefill_ms.serve_rate", "prefill_mfu.serve",
        "prefill_useful_pct.serve_rate", "attn_ms.serve", "mlp_ms.serve",
        "head_ms.serve", *NEW_METRICS}
    assert not {"state_ms.serve", "cache_read_pct.serve",
                "unscoped_pct.serve", "ssm_decode_roofline.serve",
                "experts_ms.serve"} & set(layer)
    # every share of a peak that moves what the cell reports is reported
    assert {"mfu.serve", "prefill_mfu.serve"} <= set(layer)
    # eleven cells of 24 at the least, one on four chips
    assert len(bench["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
