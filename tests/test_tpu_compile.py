"""The main path's Pallas kernels, compiled for a described TPU v5e.

No chip is attached here: the TPU compiler is installed and compiles for a
topology that is only *described* (guide ``on-chip-measurement`` §2,
rehearsal 3).  Nothing runs, so these say nothing about results or speed —
they pin what the v5e compiler accepts and refuses at the widths
``chip_smoke.py`` drives, at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under xdist every worker
imports this file.  Dispatch asks ``jax.devices()`` and would take its CPU
branch during such a compile, so the tests steer ``_on_tpu`` themselves.
"""

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import attention, delta_update, mamba_update
from ray_tpu.ops.decode_attention import decode_attention, tile_positions


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


def _qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


# (B, S, H, D): GPT-2 small (``chip_smoke.py``), a sequence of 2048, the
# training cells' own (``gpt2_medium`` at 32 rows a chip), and a head of 128
# (``models/llama.py``)
FLASH_SHAPES = [(32, 1024, 12, 64), (4, 2048, 32, 64), (32, 1024, 16, 64),
                (8, 2048, 32, 128)]


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_compiles_to_pallas(one_chip, as_if_on_tpu, shape):
    fwd = jax.jit(lambda q, k, v: attention.flash_attention(q, k, v))
    text = fwd.lower(*_qkv(shape, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_backward_compiles_to_pallas(
    one_chip, as_if_on_tpu, shape
):
    def loss(q, k, v):
        return attention.flash_attention(q, k, v).astype(jnp.float32).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = grad.lower(*_qkv(shape, one_chip)).compile().as_text()
    # the forward kernel and ONE backward kernel (dq, dk and dv together:
    # until PR 49 dq and dk/dv were a kernel each)
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_packed_flash_forward_backward_compiles_to_pallas(
    one_chip, as_if_on_tpu, shape
):
    """The packed entry over the projection's one ``[B, S, 3, H, D]``: the
    ``BlockSpec``s that address a head's plane inside ``[B, 3, H, D, S]``
    (five dims, three of them squeezed) and the backward's one result block
    ``(3, D, S)`` are what Mosaic must take.  Alone, a row-major parameter
    is turned once on its way in; that no such turn is left in the cells'
    step is ``test_training_cells_step_turns_no_kernel_operand``'s."""
    b, s, h, d = shape

    def loss(qkv):
        return attention.flash_attention_packed(qkv).astype(jnp.float32).sum()

    qkv = jax.ShapeDtypeStruct((b, s, 3, h, d), jnp.bfloat16,
                               sharding=one_chip)
    text = jax.jit(jax.grad(loss)).lower(qkv).compile().as_text()
    # the forward kernel and the one backward kernel, as the unpacked entry's
    assert text.count("tpu_custom_call") == 2


@pytest.fixture(scope="module")
def training_step(one_chip):
    """``gpt2_medium``'s whole optimizer step as ``benchmarks/jobs/
    train_dp.py`` builds it (``shard_map`` over the gang's mesh, AdamW,
    state donated; one chip, 32 x 1024 tokens), compiled for the v5e: once
    a module, ~15 s."""
    import importlib
    import json

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    root = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(root, "configs", "gpt2_medium.json")) as f:
        cell = json.load(f)
    with open(os.path.join(root, "traffic", "dp_32k.json")) as f:
        mix = json.load(f)
    fam = importlib.import_module("benchmarks.families." + cell["family"])
    cfg = fam.config(cell["model"])
    mesh = Mesh(np.array(list(one_chip.device_set)), ("data",))
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    on = lambda tree, where: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=where), tree)
    params = on(jax.eval_shape(
        lambda: fam.init(jax.random.PRNGKey(0), cfg)), whole)
    tx = optax.adamw(mix["learning_rate"])
    opt_state = on(jax.eval_shape(tx.init, params), whole)
    tokens = jax.ShapeDtypeStruct(
        (mix["global_batch"], mix["seq"] + 1), jnp.int32, sharding=split)
    votes = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=split)

    def shard_grads(p, tok, votes):
        loss, grads = jax.value_and_grad(lambda q: fam.loss(q, tok, cfg))(p)
        return (jax.lax.pmean(loss, "data"), jax.lax.pmean(grads, "data"),
                jax.lax.pmax(votes.max(), "data"))

    def step(p, o, tok, votes):
        loss, grads, stop = jax.shard_map(
            shard_grads, mesh=mesh, in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P(), P()), check_vma=False)(p, tok, votes)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss, stop

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_on_tpu", lambda: True)
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt_state, tokens, votes).compile()


def test_training_cells_step_compiles_with_its_three_kernels(training_step):
    """The forward kernel, the forward again under the layers' remat, and
    the one backward kernel.  The job calls a run incorrect under 3
    ``tpu_custom_call``s ("flash attention fell back").  The kernels ask for
    no VMEM limit of their own: that the compile passes is that they fit
    the default scoped limit."""
    assert training_step.as_text().count("tpu_custom_call") >= 3
    # 6.76 GB of temporaries beside 2.13 GB of state (the cell's file)
    assert training_step.memory_analysis().temp_size_in_bytes < 7.5e9


def test_training_cells_step_turns_no_kernel_operand(training_step):
    """The kernels take q, k and v INSIDE the projection's result, as the
    compiled step keeps it (``[B, 3, H, D, S]``: the sequence along the
    lanes), give ``out`` and take dO as ``[B*H, D, S]``, and write ONE
    d(qkv) of the projection's shape (``flash_attention_packed``, PR 59).
    So nowhere in the step, the layers' two loop bodies included, does
    anything but a product or a kernel make an array of a kernel operand's
    size, or of the packed three's.  Until PR 50 the kernels asked for
    ``[B*H, S, D]`` and a layer ran nine copies and two
    ``slice_bitcast_fusion``s around its three kernels (108 ms of an 801 ms
    step on the chip).  Until PR 59 they asked for q, k and v apart: a plain
    ``fusion`` split the projection's result a body (forward, and again
    under the layers' remat: 29.4 ms a step) and three
    ``copy_bitcast_fusion``s laid dq, dk and dv into d(qkv) (13.6 ms); both
    went with the packed entry.  An array is counted if it has a kernel
    operand's size, or three times it, and keeps the head's 64 as an axis:
    the residual stream's own ``bf16[32,1024,1024]`` copies are not the
    kernels' and not counted."""
    size = 32 * 16 * 1024 * 64
    made, products = {}, []
    for name, result, op, rest in named_instructions(training_step.as_text()):
        if op == "custom-call":
            op = re.search(r'custom_call_target="(\w+)"', rest).group(1)
        if op in PREFETCHES or op.endswith("-start"):
            continue
        shapes = [tuple(int(d) for d in dims.split(",") if d not in ("", "1"))
                  for dims in re.findall(r"bf16\[([\d,]*)\]", result)]
        if any(math.prod(dims) in (size, 3 * size) and 64 in dims
               for dims in shapes):
            kind = re.sub(r"[.\d]+$", "", name) if op == "fusion" else op
            made[kind] = made.get(kind, 0) + 1
            if kind == "fusion":
                products.append(
                    re.search(r'op_name="[^"]*/([^/"]+/[^/"]+)"', rest).group(1))
    # the kernels themselves: the forward, the forward again under the
    # layers' remat, the backward (its result the packed d(qkv))
    assert made.pop("tpu_custom_call") == 3
    # no copy, transpose, slice_bitcast_fusion or copy_bitcast_fusion, and
    # no group under another name: what is left are the products that make
    # a kernel's operand (the q/k/v projection, forward and under remat,
    # feeds the kernels as it is) and d(out) of the output projection
    assert made == {"fusion": 3}, made
    assert sorted(products) == [
        "bse,ethd->bsthd/dot_general", "bse,ethd->bsthd/dot_general",
        "bshd,hde->bse/dot_general"], products


# (L, B, H, Hkv, T, D): GPT-2 small's cache and TinyLlama's, the two shapes
# at which the Pallas decode kernel was pinned until PR 51 (compiled at the
# first, refused for VMEM at the second)
DECODE_SHAPES = [(12, 32, 12, 12, 1024, 64), (22, 32, 32, 8, 2048, 64)]


@pytest.mark.parametrize("shape", DECODE_SHAPES, ids=str)
def test_decode_attention_compiles_where_the_kernel_was_pinned(
    one_chip, shape
):
    """What the decode steps run compiles for the v5e at both shapes, as
    plain XLA (no kernel), a layer's read taken out of the stacked cache
    where it lies: no temporary the size of a layer's keys."""
    L, B, H, Hkv, T, D = shape
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    cache = s((L, B, Hkv, T, D))
    compiled = jax.jit(
        lambda q, k_cache, v_cache, pos, k_self, v_self: decode_attention(
            q, k_cache, v_cache, pos, L - 1, k_self=k_self, v_self=v_self)
    ).lower(s((B, H, D)), cache, cache, s((B,), jnp.int32),
            s((B, Hkv, D)), s((B, Hkv, D))).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (
        B * Hkv * T * D * 2)


# The serving cells' configurations (benchmarks/configs/<name>.json).
CELL_CONFIGS = ["mistral7b_l16", "longcat_flash_l4_ep32",
                "nemotron3_super_l11_ep4", "mimo_v25_l7_ep16",
                "mistral_small4_l9_ep8", "laguna_s21_l9_ep16",
                "olmo_hybrid7b_l12", "granite4h_micro", "minicpm_sala_l12"]


@pytest.fixture(scope="module")
def on_chip(one_chip):
    """``on_chip(tree)``: a tree of shapes, each on the described chip."""
    return lambda tree: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), tree)


@pytest.fixture(scope="module")
def cell(on_chip):
    """``cell(name) -> (family, model config, params, cache)``: a serving
    cell's shapes (its widths, slots and positions) on the described chip."""
    import importlib
    import json

    from ray_tpu.models import model_family

    @functools.cache
    def shapes(name):
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                               "configs", name + ".json")) as f:
            cell = json.load(f)
        cfg = importlib.import_module(
            "benchmarks.families." + cell["family"]).config(cell["model"])
        fam = model_family(cfg)
        params = on_chip(jax.eval_shape(
            lambda: fam.init(jax.random.PRNGKey(0), cfg)))
        cache = on_chip(jax.eval_shape(lambda: fam.init_cache(
            cfg, cell["engine"]["max_batch_size"],
            cell["engine"]["max_seq_len"])))
        return fam, cfg, params, cache

    return shapes


@pytest.fixture(scope="module")
def cell_decode_step(cell, on_chip):
    """``compiled(name) -> (executable, cache, params)``: a serving cell's
    decode step as ``JaxLLMEngine`` jits it (``jit_decode_step``, the
    engine's own: the counted twin where the family has one, cache donated,
    the weights' layouts the compiler's), compiled for the v5e: once a
    module, 10-13 s each."""
    from ray_tpu.llm.engine import jit_decode_step

    @functools.cache
    def compiled(name):
        fam, cfg, params, cache = cell(name)
        slots = jax.tree.leaves(cache)[0].shape[1]
        rows = on_chip(jax.ShapeDtypeStruct((slots,), jnp.int32))
        step = jit_decode_step(fam, cfg, params)
        with pytest.MonkeyPatch.context() as patch:  # dispatch asks the CPU
            patch.setattr(attention, "_on_tpu", lambda: True)
            lowered = step.lower(params, cache, rows, rows)
        return lowered.compile(), cache, params

    return compiled


def test_longcat_decode_step_fits_beside_its_weights(cell_decode_step):
    """The LongCat decode step at the benchmark cell's size (published
    widths, 4 double layers, 16 experts held, 32 slots x 2048) compiles for
    the v5e with temporaries far under the weights it reads: a layer's slice
    of the stacked experts taken outside the expert loop, or a layer's
    weights sliced first and indexed later, is COPIED every step (4.6 GB and
    3.6 GB, PERF.md PR 29) and the compiler then refuses the program beside
    11 GB of arguments."""
    memory = cell_decode_step("longcat_flash_l4_ep32")[0].memory_analysis()
    assert memory.argument_size_in_bytes > 10.9e9
    # 0.13 GB, the eight ``wkv_b`` out of their stack; 0.68 GB while each
    # attention's [32, 2048, 576] slice of the cache was copied out of it
    # for its products (until PR 46: the read side)
    assert memory.temp_size_in_bytes < 0.2e9


@pytest.mark.parametrize("name", CELL_CONFIGS)
def test_decode_step_writes_its_token_into_the_cache_in_place(
    cell_decode_step, name
):
    """Nothing but the in-place update of one tile a slot produces an array
    the size of a cache leaf.  Before ``write_token_to_cache`` was that, the
    Mistral step held four ``copy`` of ``bf16[16,16,8,2048,128]`` (the
    operand of a scatter relaid T-major and back, for k and for v: 12.8 of
    its 28.6 ms on the chip, ``temp`` 1.085 GB), and the LongCat step a
    ``select`` fusion over the whole latent cache."""
    compiled, cache, _ = cell_decode_step(name)
    # bf16[16,16,8,2048,128] twice; bf16[8,32,2048,576]; the hybrid's
    # bf16[1,64,2,2048,128] twice and bf16[5,64,10240,3] (its float32 state
    # has a test of its own, below); MiMo's two extents:
    # bf16[2,64,4,4096,192|128] and the rings bf16[5,64,8,128,192|128];
    # Laguna's: bf16[3,32,8,16384,128] and rings of four lane tiles,
    # bf16[6,32,8,512,128]
    leaves = {"bf16[%s]" % ",".join(map(str, leaf.shape)): leaf.size * 2
              for leaf in jax.tree.leaves(cache)}
    producers = set(re.findall(
        rf"^\s*(?:ROOT )?%(\S+) = ({'|'.join(map(re.escape, leaves))})\S* "
        rf"([\w-]+)\(", compiled.as_text(), re.M))
    # The update is a ``dynamic-update-slice`` or, where the compiler can
    # see the tile is aligned, its fusion of read, select and write into
    # one (``select_dynamic-update-slice_fusion``): named after its root.
    updates = {name for name, _, _ in producers
               if re.search("dynamic[-_]update[-_]slice", name)}
    assert updates
    # ``copy-start`` / ``copy-done`` of a leaf under 100 MB (the hybrid's
    # 67 MB of keys, and of values) is a prefetch into the chip's fast
    # memory and back; of Mistral's 1.07 GB it would be the copy again.
    others = {(name, leaf, op) for name, leaf, op in producers
              if name not in updates
              and op not in ("parameter", "get-tuple-element")
              and not (op == "copy-done" and leaves[leaf] < 100e6)}
    assert not others
    if name == "mistral7b_l16":
        # 0.005 GB; 0.745 while every step copied ``wq`` / ``wk`` / ``wv``
        # out of their stacks (the weights' default layout: below)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9


# A cell's full-extent attentions, and the most its decode step may hold in
# temporaries (the limits of the tests around this one).  In the cells of
# ``LATENT_READS`` each of them is ONE call of ``ops/latent_attention.py``'s
# kernel and no loop (PR 68); in the others one loop over blocks.
LATENT_READS = ("longcat_flash_l4_ep32", "mistral_small4_l9_ep8")
BOUNDED_READS = {"mistral7b_l16": (16, 0.05e9),
                 "longcat_flash_l4_ep32": (8, 0.2e9),
                 "nemotron3_super_l11_ep4": (1, 0.05e9),
                 "mimo_v25_l7_ep16": (2, 0.05e9),
                 "mistral_small4_l9_ep8": (9, 0.05e9),
                 "laguna_s21_l9_ep16": (3, 0.05e9),
                 "olmo_hybrid7b_l12": (3, 0.05e9),
                 "granite4h_micro": (4, 0.05e9),
                 # no contiguous read: its three sparse layers read a LIST
                 # of blocks (``attend_listed_blocks``; the test of the
                 # MiniCPM-SALA cell below), and nothing of a prefix's shape
                 "minicpm_sala_l12": (0, 0.05e9)}


@pytest.mark.parametrize("name", CELL_CONFIGS)
def test_decode_step_reads_its_live_blocks_where_they_lie(
    cell_decode_step, name
):
    """Every full-extent attention of the step is ONE loop over blocks of
    ``extent_step(T)`` positions (``ops/decode_attention.py``
    ``attend_live_blocks``) or, where the cache holds latents (LongCat,
    Mistral-4), ONE call of ``ops/latent_attention.py``'s kernel, whose grid
    is that loop: eight and nine calls of one lowered kernel, each handed
    the donated ``latent`` leaf WHOLE, as a view of the bytes where they lie
    (``latent_kernels``).  In no computation of the module, the loops'
    bodies included, does anything produce an array of a cache prefix's
    shape (a leaf's slice of fewer whole steps than it has, or fewer of its
    last axis) in
    the chip's main memory: the products read each block out of the donated
    stack.  A block copied first is three passes over it where there was one
    (what each LongCat attention did to its WHOLE ``[32, 2048, 576]`` slice
    until PR 46: 0.60 GB of temporaries, 1.59 ms a step).  XLA may hold ONE
    block in the fast memory (``S(1)``) for a loop's products; the latent
    layers' loops did, and ran copy, products, copy in turn (5.26 of the
    Mistral-4 step's 13.4 ms: PERF.md, PR 58) until the kernel's double
    buffer fetched block ``j + 1`` under block ``j``'s products: no XLA
    instruction makes a block of latents any more, in any memory."""
    import json

    from ray_tpu.ops.decode_attention import extent_step

    compiled, cache, _ = cell_decode_step(name)
    text = compiled.as_text()
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", name + ".json")) as f:
        t = json.load(f)["engine"]["max_seq_len"]
    step = extent_step(t)
    assert step == 512 < t
    reads, temp_limit = BOUNDED_READS[name]
    loops = len(re.findall(
        r' while\(.*op_name="[^"]*(?:decode_attention\)|longcat\.mla|mistral4\.mla)'
        r'/while"',
        text))
    if name in LATENT_READS:
        assert loops == 0
        assert len(latent_kernels(text, cache["latent"])) == reads
        assert not re.search(r"bf16\[[\d,]*\b512,(?:320|576)\]", text)
    else:
        assert loops == reads
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit
    prefixes = {}  # dims without the 1s -> steps
    for leaf in jax.tree.leaves(cache):
        if t in leaf.shape[2:]:
            axis = leaf.shape.index(t, 2)
            for steps in range(1, t // step):
                dims = leaf.shape[1:axis] + (steps * step,) + leaf.shape[
                    axis + 1:]
                prefixes[tuple(d for d in dims if d != 1)] = steps
    assert prefixes
    made = []
    for result, op, _ in instructions(text):
        for dims, layout in re.findall(
                r"bf16\[([\d,]*)\](?:\{([^}]*)\})?", result):
            dims = tuple(int(d) for d in dims.split(",")
                         if d not in ("", "1"))
            steps = max((n for part, n in prefixes.items()
                         if len(part) == len(dims)
                         and part[:-1] == dims[:-1]
                         and dims[-1] <= part[-1]), default=0)
            if steps and not (steps == 1 and "S(1)" in layout):
                made.append((op, dims, layout))
    assert not made


def latent_kernels(text, leaf):
    """The names of a compiled step's ``ops.latent_attention`` kernels: custom
    calls named ``latent_attention`` (one lowered kernel: the layer rides in
    the prefetched scalars) whose third operand, after those scalars and the
    queries, is the latent leaf ``[A, B, T, C]`` WHOLE, as the kernel sees
    the bytes the program holds: ``[A, B, C, T]`` row-major, ONE ``bitcast``
    of the donated parameter (positions minor: ``C`` is no multiple of 128),
    not a copy, a transpose or a slice of it."""
    a, b, t, c = leaf.shape
    held = re.escape(f"bf16[{a},{b},{t},{c}]{{2,3,1,0:T(8,128)(2,1)}}")
    seen = re.escape(f"bf16[{a},{b},{c},{t}]{{3,2,1,0:T(8,128)(2,1)}}")
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text,
                      re.M | re.S).group(1)
    donated = re.findall(rf"^\s*%(\S+) = {held} parameter\(", entry, re.M)
    assert len(donated) == 1
    views = re.findall(rf"^\s*%(\S+) = {seen} ([\w-]+)\(%([\w.-]+)\)", text,
                       re.M)
    assert [(op, of) for _, op, of in views] == [("bitcast", donated[0])]
    calls = re.findall(
        r"^\s*(?:ROOT )?%(\S+) = f32\[[\d,]+\]\S* custom-call\(%[\w.-]+, "
        r"%[\w.-]+, "
        r'%([\w.-]+), [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'/latent_attention/pallas_call"', text, re.M)
    assert {operand for _, operand in calls} <= {views[0][0]}
    assert len(calls) == text.count("/latent_attention/pallas_call")
    return [name for name, _ in calls]


def mamba_kernels(text, shape):
    """The names of a compiled step's ``ops.mamba_update`` kernels: custom
    calls whose first result is the ``ssm`` leaf ``f32[shape]`` as the
    program holds it (last axis minor, no padding), aliased to their third
    operand (after the layer, a constant of each call of the ONE lowered
    kernel, and the heads' ``keep``)."""
    leaf = re.escape(f"f32[{shape}]{{4,3,2,1,0:T(8,128)}}")
    assert re.search(leaf, text)
    return re.findall(
        rf"^\s*%(\S+) = \({leaf}, [^\n]*?\) custom-call\(%constant[\w.]*, "
        r'[^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        + re.escape("output_to_operand_aliasing={{0}: (2, {})}")
        + r'[^\n]*/mamba_update/pallas_call"', text, re.M)


def leaf_is_only_handed_on(text, shape):
    """Nothing but a program's parameter and its kernels' results produces
    an array of the leaf's shape, and no instruction copies, slices or
    updates a slice of one: the kernels are the only readers and writers of
    the state."""
    producers = dict(re.findall(
        rf"^\s*(?:ROOT )?%(\S+) = f32\[{shape}\]\S* ([\w-]+)\(", text, re.M))
    touched = [line for line in text.splitlines()
               if f"[{shape}]" in line and re.search(
                   r" (copy|fusion|dynamic-update-slice|slice)\(", line)]
    return set(producers.values()) <= {
        "parameter", "get-tuple-element"} and not touched


def window_traffic(text, leaf):
    """What a compiled step's ENTRY does with the convolutions' stacked
    windows ``leaf [layers, slots, (K-1) C]`` (``ops/conv_update.py``): the
    ops that produce an array of the leaf's shape, the ops that produce an
    array of ONE layer's shape (a fusion of several outputs once an output),
    and the names among both that are rematerialised clones.  Views and the
    ``-start`` half of an asynchronous pair apart."""
    layers, slots, width = leaf.shape
    one = re.compile(rf"f32\[(?:1,)?{slots},{width}\]")
    made, slices, clones = [], [], []
    for name, result, op, _ in named_instructions(text, entry_only=True):
        if op.endswith("-start"):
            continue
        whole = f"f32[{layers},{slots},{width}]" in result
        made += [op] * whole
        slices += [op] * len(one.findall(result))
        if "remat" in name and (whole or one.search(result)):
            clones.append(name)
    return made, slices, clones


def test_hybrid_decode_step_updates_its_recurrent_state_where_it_lies(
    cell_decode_step
):
    """The Nemotron-H cell's decode step (published widths, one period of
    11 layers, 128 experts held, 64 slots x 2048): every element of the 1.34
    GB ``ssm`` leaf changes every step, so the least a step can do is read
    it once and write it once, where it lies, and that is what it does: each
    Mamba-2 layer is ONE Pallas kernel (``ops/mamba_update.py``) whose
    operand and result are the WHOLE donated leaf, aliased, and whose blocks
    are that layer's slots (XLA's own step was a fusion rooted at the
    ``dynamic-update-slice`` of the layer's slice and a reduce fusion that
    read it again: three crossings, until PR 61).  NO fusion produces an
    array of the leaf's shape, nothing slices or copies it, and the step's
    temporaries stay far under one layer's slice (268 MB): a stack of the
    layers' new states at the step's end, or a slice copied out for its
    products, would be 1.34 GB more a step (PR 34's lesson).  The 39 MB
    ``conv`` leaf goes through the same five layers whole
    (``ops/conv_update.py``): five fusions take a layer's window out of it,
    five more, rooted at the scatter, write the shifted window where it lay,
    and none of the ten is a clone.  What else produces an array of the
    leaf's or a layer's shape only moves it between the chip's memories: the
    compiler prefetches the whole leaf into the fast memory, runs the middle
    layers' updates there and copies it back ONCE, as it did the stack of
    the new windows that the step built until PR 63 (32 asynchronous slices
    of the leaf then, and 45 MB of temporaries where 13 stand)."""
    compiled, cache, _ = cell_decode_step("nemotron3_super_l11_ep4")
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 10.8e9
    assert memory.alias_size_in_bytes > 1.5e9  # the whole cache, donated
    assert memory.temp_size_in_bytes < 0.05e9  # 0.013 GB
    shape = ",".join(map(str, cache["ssm"].shape))
    assert shape == "5,64,128,64,128"
    text = compiled.as_text()
    # one a Mamba-2 layer, and the step has no other kernel
    assert len(mamba_kernels(text, shape)) == 5 == text.count(
        "tpu_custom_call")
    assert leaf_is_only_handed_on(text, shape)
    assert cache["conv"].shape == (5, 64, 3 * 10240)
    made, slices, clones = window_traffic(text, cache["conv"])
    assert not clones
    assert sorted(made) == ["copy-done", "custom-call", *["fusion"] * 5]
    assert slices.count("fusion") == 5
    assert set(slices) <= {"fusion", "copy-done", "slice-done", "custom-call"}


def test_windowed_decode_step_fits_and_its_top_rung_beside_it(
    cell, cell_decode_step, on_chip
):
    """The MiMo cell's programs at its size (published widths, 7 layers, 16
    experts held, 64 slots x 4096): the decode step's temporaries are a
    thousandth of what it reads (no slice of a cache leaf or of an expert
    stack copied out: 0.013 GB), the whole cache is aliased to its output,
    and the top prefill rung, which scores a window layer's band alone and a
    full layer in blocks of 512 queries, needs 1.44 GB beside 8.42 GB of
    arguments (dense ``[64, 4096, 4096]`` float32 scores would be 4.3 GB a
    layer)."""
    from ray_tpu.llm.engine import jit_prefill_one

    step, cache, params = cell_decode_step("mimo_v25_l7_ep16")
    memory = step.memory_analysis()
    assert 8.3e9 < memory.argument_size_in_bytes < 8.6e9
    assert memory.alias_size_in_bytes > 1.5e9  # rings and full leaves
    assert memory.temp_size_in_bytes < 0.05e9
    assert cache["k_win"].shape == (5, 64, 8, 128, 192)
    assert cache["v"].shape == (2, 64, 4, 4096, 128)
    fam, cfg, _, _ = cell("mimo_v25_l7_ep16")
    lying = jax.tree.map(lambda leaf, fmt: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=fmt), params,
        step.input_formats[0][0])
    tokens = on_chip(jax.ShapeDtypeStruct((4096,), jnp.int32))
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    rung = jit_prefill_one(fam, cfg).lower(
        lying, cache, tokens, scalar, scalar).compile()
    assert rung.memory_analysis().temp_size_in_bytes < 2.0e9


def moe_row_ops(text):
    """{(result array, op)} of every ``gather`` and ``scatter`` a compiled
    program runs under an expert layer's scope (``<family>.moe``), fusions'
    own computations included: what the layer moves by row or element
    index."""
    return {(result, op) for result, op, name in re.findall(
        r'= (\w+\[[\d,]*\])\S* (gather|scatter)\([^\n]*op_name="([^"]*)"',
        text) if ".moe/" in name}


def test_latent_long_decode_step_fits_and_its_top_rung_beside_it(
    cell, cell_decode_step, on_chip
):
    """The Mistral-4 cell's programs at its size (published widths, 9
    layers, 16 experts held, 32 slots x 16,384): the decode step's arguments
    are the weights and a cache of 9 x 32 x 16384 x 320 x 2 B = 3.02 GB (the
    TPU lays positions on the lanes and the 320 on the sublanes: NOT padded
    to 384), all of it aliased to the output, its temporaries a thousandth
    of what it reads (no slice of the cache or of an expert stack copied
    out); the compiler asks for no other layout of an expert stack (the
    build moves relaid leaves while holding them twice: 7.25 GB would not
    fit); and the top prefill rung, whose layers are one scanned body and
    whose scores exist a tile of 512 x 512 at a time, needs 1.4 GB beside
    them (dense ``[32, 16384, 16384]`` float32 scores would be 34 GB a layer;
    nine layers written out kept 5.05 GB of temporaries)."""
    from ray_tpu.llm.engine import jit_prefill_one

    step, cache, params = cell_decode_step("mistral_small4_l9_ep8")
    memory = step.memory_analysis()
    assert cache["latent"].shape == (9, 32, 16384, 320)
    assert 11.4e9 < memory.argument_size_in_bytes < 11.6e9
    assert 3.01e9 < memory.alias_size_in_bytes < 3.03e9
    assert memory.temp_size_in_bytes < 0.05e9
    formats = step.input_formats[0][0]
    assert all(fmt.layout.major_to_minor == (0, 1, 2, 3)
               for fmt in formats["experts"].values())
    fam, cfg, _, _ = cell("mistral_small4_l9_ep8")
    lying = jax.tree.map(lambda leaf, fmt: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=fmt), params, formats)
    tokens = on_chip(jax.ShapeDtypeStruct((16384,), jnp.int32))
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    rung = jit_prefill_one(fam, cfg).lower(
        lying, cache, tokens, scalar, scalar).compile()
    assert rung.input_formats[0][0] == formats
    assert rung.memory_analysis().temp_size_in_bytes < 2.0e9
    assert rung.memory_analysis().generated_code_size_in_bytes < 20e6
    # an expert layer's turn: 256 rows gathered, their weights, the chunk
    # added in place; nothing else there moves a row or an element by index
    assert moe_row_ops(rung.as_text()) == {
        ("bf16[256,4096]", "gather"), ("f32[256]", "gather"),
        ("f32[16384,4096]", "scatter")}


def test_latent_long_decode_step_reads_a_touched_expert_where_it_lies(
    cell_decode_step
):
    """The Mistral-4 cell's decode step takes the held experts' loop (32 x 4
    choices for 128 experts: ``expert_share.runs_every_held_expert``) in its
    one-chunk form: nine loops, one an expert layer, whose body is three
    products on the whole batch.  Each product's fusion is handed the WHOLE
    ``[9, 16, d, f]`` stack and slices its expert's matrix out by the loop's
    own index, inside the fusion; the body makes nothing larger than
    ``[32, 4096]`` float32; no computation of the program that is not a
    fusion's own produces a stack, a layer's slice of one or an expert's
    matrix (805 MB a layer copied would cost more than the step); and
    nothing under the layer's scope moves by row or element index (the
    sort, the gather and the scatter-add are the longer loops')."""
    step, _, params = cell_decode_step("mistral_small4_l9_ep8")
    text = step.as_text()
    bodies = dict(re.findall(
        r"^%([\w.-]+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S))
    loops = [bodies[name] for name in re.findall(
        r"while\([^\n]*body=%([\w.-]+)", text)
        if "mistral4.moe/while/body" in bodies[name]]
    assert len(loops) == 9
    stacks = {"bf16[9,16,4096,2048]", "bf16[9,16,2048,4096]"}
    for body in loops:
        products = re.findall(
            r"= (\w+\[[\d,]*\])\S* fusion\([^\n]*calls=%([\w.-]+)"
            r"[^\n]*dot_general", body)
        assert len(products) == 3
        for result, fused in products:
            assert result in ("f32[32,2048]", "bf16[32,2048]", "f32[32,4096]")
            taken = set(re.findall(
                r"= (\w+\[[\d,]*\])\S* parameter\(", bodies[fused]))
            assert taken & stacks and "dynamic-slice" in "".join(
                bodies[name] for name in re.findall(
                    r"calls=%([\w.-]+)", bodies[fused]))
        made = [math.prod(map(int, dims.split(","))) for dims, op in re.findall(
            r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(", body, re.M)
            if op not in VIEWS]
        assert max(made) == 32 * 4096
    assert not copied_weights(text, params, entry_only=False)
    assert moe_row_ops(text) == set()


def test_ring_long_decode_step_fits_and_its_top_rung_beside_it(
    cell, cell_decode_step, on_chip
):
    """The Laguna cell's programs at its size (published widths, 9 layers,
    16 experts held, 32 slots x 16,384): the decode step's arguments are the
    weights (4.00 GB) and a cache of 3 x 32 x 16384 x 4 KB = 6.44 GB of full
    keys and values beside 0.40 GB of rings, all of it aliased to the
    output, its temporaries a thousandth of what it reads (no slice of a
    cache leaf or of an expert stack copied out, and query groups of 6 and
    9, neither a power of two nor a multiple of the sublanes, cost ONE copy
    a layer of the float32 query before it is rounded, ``[32, 8, 9, 128]`` =
    1.2 MB, the window layers' in the chip's fast memory: microseconds of a
    13 ms step; nothing else in the step is copied or transposed at a
    megabyte); the compiler asks for ``[L, H, E, D]`` of the
    projections and the gate (0.57 GB relaid at the build) and for no other
    layout of an expert stack; and the top prefill rung, whose layers are
    THREE scanned bodies (``laguna.layer_plan``) and whose scores exist a
    tile of 512 x 512 at a time over grouped heads, needs 2.9 GB beside
    them (the band of 72 heads whole would be 4.8 GB, a full layer's 512
    queries against all keys 1.6 GB; the 16,384 x 12,288 hidden state of
    layer 0's dense MLP is 0.8 GB in float32) in 24 MB of code (31 with five
    bodies: seven rungs and the step must stay inside the chip's compile
    cache, ~190 MiB)."""
    from ray_tpu.llm.engine import jit_prefill_one

    step, cache, params = cell_decode_step("laguna_s21_l9_ep16")
    memory = step.memory_analysis()
    assert cache["k"].shape == (3, 32, 8, 16384, 128)
    assert cache["v_win"].shape == (6, 32, 8, 512, 128)
    assert 10.8e9 < memory.argument_size_in_bytes < 10.9e9
    assert 6.84e9 < memory.alias_size_in_bytes < 6.85e9
    assert memory.temp_size_in_bytes < 0.05e9
    assert memory.generated_code_size_in_bytes < 20e6
    formats = step.input_formats[0][0]
    assert all(fmt.layout.major_to_minor == (0, 1, 2, 3)
               for fmt in formats["experts"].values())
    for kind in ("full", "window"):
        moved = {k: f.layout.major_to_minor
                 for k, f in formats["blocks"][kind].items()
                 if f.layout.major_to_minor != tuple(range(len(
                     f.layout.major_to_minor)))}
        assert moved == dict(dict.fromkeys(("wq", "wk", "wv"), (0, 2, 1, 3)),
                             wg=(0, 2, 1))
    # what a group of 6 or 9 costs: the query's copy, and nothing larger
    copied = [(op, dtype, dims) for result, op, _ in instructions(
        step.as_text()) if op in ("copy", "transpose")
        for dtype, dims in re.findall(r"(bf16|f32)\[([\d,]+)\]", result)
        if math.prod(map(int, dims.split(","))) * (
            2 if dtype == "bf16" else 4) > 0.5e6]
    assert len(copied) <= 9 and set(copied) <= {
        ("copy", "f32", "32,8,6,128"), ("copy", "f32", "32,8,9,128")}
    fam, cfg, _, _ = cell("laguna_s21_l9_ep16")
    lying = jax.tree.map(lambda leaf, fmt: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=fmt), params, formats)
    tokens = on_chip(jax.ShapeDtypeStruct((16384,), jnp.int32))
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    rung = jit_prefill_one(fam, cfg).lower(
        lying, cache, tokens, scalar, scalar).compile()
    assert rung.input_formats[0][0] == formats
    assert rung.memory_analysis().temp_size_in_bytes < 3.2e9
    assert rung.memory_analysis().generated_code_size_in_bytes < 27e6
    # an expert layer's turn: 256 rows gathered, their weights, the chunk
    # added in place; the router's chosen scores are a select, not
    # ``take_along_axis``'s gather of 16,384 x 10 scalars (1.7 ms a layer on
    # the chip, PERF.md, PR 53): nothing else moves by index
    assert moe_row_ops(rung.as_text()) == {
        ("bf16[256,3072]", "gather"), ("f32[256]", "gather"),
        ("f32[16384,3072]", "scatter")}
    # 15.75 GB of the chip: arguments + the rung's temporaries + 0.26 held
    assert (memory.argument_size_in_bytes
            + rung.memory_analysis().temp_size_in_bytes) < 14.5e9


def test_delta_decode_step_updates_its_packed_state_where_it_lies(
    cell, cell_decode_step, on_chip
):
    """The Olmo-Hybrid cell's programs at its size (published widths, 12
    layers ``FLLL x 3``, the whole vocabulary, 64 slots x 2048): the decode
    step's arguments are the weights (6.54 GB) and a cache of 3 x 64 x 2048 x
    15,360 B = 6.04 GB of keys and values beside 1.35 GB of state, all of it
    aliased to the output.  The delta rule's state lies TWO heads to a row
    (``f32[9,64,15,96,384]``, ``olmo_hybrid.pack_state``): a head's ``[96,
    192]`` float32 matrix alone, either way up, is padded to 256 lanes (or
    its 96 to 128) and a third more is held, read and written every step
    (read off the compiler below: 1.333 against 1.000).  Every element of
    the leaf changes every step, so the least a step can do is read it and
    write it, where it lies, and that is what it does: each linear layer is
    ONE Pallas kernel (``ops/delta_update.py``) whose operand and result are
    the WHOLE donated leaf, aliased, and whose blocks are that layer's
    slots: ``S^T k``, ``S^T q`` and ``alpha S + k (x) delta`` on a block
    held in fast memory (XLA's own step was a reduce fusion over the layer's
    slice and a second fusion, rooted at the ``dynamic-update-slice``, that
    read it again: PR 56).  Nothing else produces or reads an array of the
    leaf's or of a layer's shape: no slice, no ``dynamic-update-slice``, no
    ``copy``.  The 80 MB ``conv`` leaf goes through the same nine layers
    whole (``ops/conv_update.py``): nine fusions take a layer's window out
    of it into the fast memory, nine more, rooted at the scatter, write the
    shifted window where it lay, none a clone and no copy of the leaf (three
    of the windows pass through the main memory on their way: the
    compiler's eviction, a ``copy-done``); while the step stacked the new
    windows at its end the compiler cut the donated leaf into seventeen
    rematerialised slices and held 0.11 GB of temporaries (until PR 63).
    The four rungs are two layer bodies each, scanned
    (``olmo_hybrid.layer_plan``): 12.6-16.9 MB of code a rung where the top
    rung written out is 59 MB (the chip's compile cache holds ~190 MiB for
    all cells' programs), 0.57 GB of temporaries at the top rung (1.08
    written out), the weights read where they lie in the decode step's
    layouts."""
    from ray_tpu.llm.engine import jit_prefill_one

    step, cache, params = cell_decode_step("olmo_hybrid7b_l12")
    memory = step.memory_analysis()
    assert cache["k"].shape == cache["v"].shape == (3, 64, 30, 2048, 128)
    assert cache["state"].shape == (9, 64, 15, 96, 384)
    assert cache["conv"].shape == (9, 64, 3 * 11520)
    assert 13.9e9 < memory.argument_size_in_bytes < 13.95e9
    assert 7.39e9 < memory.alias_size_in_bytes < 7.40e9  # the whole cache
    assert memory.temp_size_in_bytes < 0.05e9  # 0.025 GB
    assert memory.generated_code_size_in_bytes < 25e6
    text = step.as_text()
    made, slices, clones = window_traffic(text, cache["conv"])
    assert not clones
    assert made == ["fusion"] * 9
    assert slices.count("fusion") == 9 and set(slices) <= {
        "fusion", "copy-done"}
    shape = ",".join(map(str, cache["state"].shape))
    # the leaf as the program holds it: last axis minor, no padding
    leaf = re.escape(f"f32[{shape}]{{4,3,2,1,0:T(8,128)}}")
    assert re.search(leaf, text)
    # nine kernels, each from the leaf (the parameter, then the kernel
    # before it) to the leaf, in the same bytes; the layer is their first
    # operand, a constant of each call of the ONE lowered kernel
    kernels = re.findall(
        rf"^\s*%(\S+) = \({leaf}, [^\n]*?\) custom-call\(%constant[\w.]*, "
        r'%([\w.-]+), [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        + re.escape("output_to_operand_aliasing={{0}: (1, {})}"), text, re.M)
    assert len(kernels) == 9 == text.count("tpu_custom_call")
    producers = dict(re.findall(
        rf"^\s*(?:ROOT )?%(\S+) = f32\[{shape}\]\S* ([\w-]+)\(", text, re.M))
    assert set(producers.values()) == {"parameter", "get-tuple-element"}
    assert {operand for _, operand in kernels} <= set(producers)
    # no instruction makes or reads a layer's [64, 15, 96, 384] of it, and
    # none copies or updates a slice of the leaf
    assert "15,96,384]" not in text.replace(f"[{shape}]", "")
    assert not [line for line in text.splitlines()
                if f"[{shape}]" in line and re.search(
                    r" (copy|fusion|dynamic-update-slice|slice)\(", line)]
    # what one head a row would cost, either way up
    held = {}
    for dims in ((64, 30, 96, 192), (64, 30, 192, 96), (64, 15, 96, 384)):
        leaf = on_chip(jax.ShapeDtypeStruct(dims, jnp.float32))
        held[dims] = jax.jit(lambda a: a * 2).lower(
            leaf).compile().memory_analysis().argument_size_in_bytes
    assert held[(64, 15, 96, 384)] == 64 * 15 * 96 * 384 * 4
    assert held[(64, 30, 96, 192)] == held[(64, 30, 192, 96)] == (
        64 * 30 * 96 * 256 * 4)
    fam, cfg, _, _ = cell("olmo_hybrid7b_l12")
    formats = step.input_formats[0][0]
    lying = jax.tree.map(lambda leaf, fmt: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=fmt), params, formats)
    tokens = on_chip(jax.ShapeDtypeStruct((2048,), jnp.int32))
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    rung = jit_prefill_one(fam, cfg).lower(
        lying, cache, tokens, scalar, scalar).compile()
    assert rung.input_formats[0][0] == formats
    assert rung.memory_analysis().temp_size_in_bytes < 0.7e9
    assert rung.memory_analysis().generated_code_size_in_bytes < 20e6
    # the chunk's (I + A)^-1 is the TPU's own blocked inverse, once a
    # linear layer's body: no loop of 64 rows
    assert len(re.findall(
        'custom_call_target="InvertDiagBlocksLowerTriangular"',
        rung.as_text())) == 1
    # 15.75 GB of the chip: arguments + the rung's temporaries + 0.26 held
    assert (memory.argument_size_in_bytes
            + rung.memory_analysis().temp_size_in_bytes) < 15.0e9


def test_whole_model_decode_step_updates_forty_layers_of_cache_in_place(
    cell, cell_decode_step, on_chip
):
    """The Granite-4.0-H cell's programs at its size (every published width,
    all 40 layers, the whole vocabulary once: the head is the table; 64
    slots x 2048): the decode step's arguments are 6.38 GB of weights and a
    cache of 6.03 GB, of which the ``ssm`` leaf alone is 4.83 GB (36 x 64
    slots x 64 heads x [64, 128] float32), the largest thing on the chip
    after the weights: all of the cache is aliased to the output.  Every
    Mamba-2 layer's update is ONE Pallas kernel (``ops/mamba_update.py``)
    from the donated leaf to itself: thirty-six calls of the one lowered
    kernel, nothing else produces, slices or copies an array of the leaf's
    shape (a second leaf would not fit), and the step's temporaries are a
    sixth of a layer's slice (137 MB).  NONE of the kernels is a clone: while
    the updates were XLA fusions the compiler rematerialised layer 0's at
    this size (not at 32 slots; ``...remat``, ``...remat2``: one fed layer
    1's read-out, one layer 1's update, both written in place over the same
    slice), which stepped layer 0's state twice a step on the chip
    (PERF.md, PR 60).  And the temporaries pin a second thing: with the
    kernels in, the compiler wrote the new values into ``v`` ahead of the
    last attention layer's read and copied the 0.54 GB leaf twice a step
    (0.60 GB of temporaries) until the step held its cache writes behind an
    ``optimization_barrier`` (PR 61).  The 120 MB ``conv`` leaf goes through
    the same thirty-six layers whole (``ops/conv_update.py``): ONE fusion a
    layer takes the layer's ``[64, 13056]`` window out of it into the fast
    memory and one more, rooted at the scatter, writes the shifted window
    where it lay; nothing else produces an array of the leaf's or of a
    layer's shape, none of the seventy-two is a clone and nothing copies the
    leaf.  While the step took ``cache["conv"][i]`` a layer and stacked the
    new windows at its end, the leaf it read WAS the donated buffer the
    stack was written into: the compiler copied every slice out first and
    rematerialised the copies, 38 fusions with 414 outputs of 3.3 MB a step
    (3.8 ms of 31 on the chip, 124 MB of temporaries: PERF.md, PR 63); and
    without ``conv_update``'s ``optimization_barrier`` layer 0's scatter is
    cloned for its second reader (``fusion.815.remat``), as PR 60's updates
    of the ``ssm`` leaf were.  Keys and values lie positions-minor
    (heads of 64 on the sublanes, 2048 positions on the lanes): NOT padded
    to 128, 0.54 GB each.  The forty layers are written out (40 MB of code,
    53 while each update was two fusions); the top rung folds them into five
    bodies (19 MB) and needs 0.29 GB beside the arguments."""
    from ray_tpu.llm.engine import jit_prefill_one

    step, cache, params = cell_decode_step("granite4h_micro")
    memory = step.memory_analysis()
    assert cache["ssm"].shape == (36, 64, 64, 64, 128)
    assert cache["conv"].shape == (36, 64, 3 * 4352)
    assert cache["k"].shape == cache["v"].shape == (4, 64, 8, 2048, 64)
    assert "lm_head" not in params
    assert 12.40e9 < memory.argument_size_in_bytes < 12.42e9
    assert 6.02e9 < memory.alias_size_in_bytes < 6.03e9  # the whole cache
    assert memory.temp_size_in_bytes < 0.05e9  # 0.023 GB
    assert memory.generated_code_size_in_bytes < 70e6  # 41 MB
    text = step.as_text()
    shape = ",".join(map(str, cache["ssm"].shape))
    assert re.search(re.escape(f"f32[{shape}]{{4,3,2,1,0:T(8,128)}}"), text)
    # one kernel a Mamba-2 layer, thirty-six calls of the ONE lowered
    # kernel, the step's only kernels; NONE is a clone
    kernels = mamba_kernels(text, shape)
    assert len(kernels) == 36 == text.count("tpu_custom_call")
    assert not [name for name in kernels if "remat" in name]
    assert leaf_is_only_handed_on(text, shape)
    # the windows: one slice and one in-place update a layer, no clone
    made, slices, clones = window_traffic(text, cache["conv"])
    assert not clones
    assert made == slices == ["fusion"] * 36
    # keys and values: positions on the lanes, no padding of the heads' 64
    kv = ",".join(map(str, cache["k"].shape))
    assert re.search(re.escape(f"bf16[{kv}]{{3,4,2,1,0:T(8,128)(2,1)}}"), text)
    held = jax.jit(lambda a: a * 2).lower(on_chip(jax.ShapeDtypeStruct(
        cache["k"].shape, jnp.bfloat16))).compile().memory_analysis()
    assert held.argument_size_in_bytes == 4 * 64 * 8 * 2048 * 64 * 2
    fam, cfg, _, _ = cell("granite4h_micro")
    formats = step.input_formats[0][0]
    lying = jax.tree.map(lambda leaf, fmt: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=fmt), params, formats)
    tokens = on_chip(jax.ShapeDtypeStruct((2048,), jnp.int32))
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    rung = jit_prefill_one(fam, cfg).lower(
        lying, cache, tokens, scalar, scalar).compile()
    assert rung.input_formats[0][0] == formats
    assert rung.memory_analysis().alias_size_in_bytes > 6.02e9
    assert rung.memory_analysis().temp_size_in_bytes < 0.4e9  # 0.29 GB
    assert rung.memory_analysis().generated_code_size_in_bytes < 25e6
    # the splice of a 77 MB row lands in the donated leaf: nothing in the
    # rung's ENTRY makes a second one
    assert not [op for result, op, _ in instructions(rung.as_text(), True)
                if f"f32[{shape}]" in result and op == "copy"]
    assert (memory.argument_size_in_bytes
            + rung.memory_analysis().temp_size_in_bytes) < 13.0e9


def test_sala_decode_step_selects_and_reads_its_blocks_where_they_lie(
    cell, cell_decode_step, on_chip
):
    """The MiniCPM-SALA cell (published widths, layers 9-20, 8 slots x
    16,384): three kinds of position-bearing leaf beside a float32 state,
    all 0.57 GB of cache aliased to the step's output, temporaries of a few
    megabytes.  The nine lightning layers are nine calls of THE
    ``ops/mamba_update.py`` kernel at one group a head (``[128, 128]`` a
    head: sixteen registers where Granite's head is eight; the kernel as it
    stood), the step's only kernels, over the ``state`` leaf as the compiler
    lays it (last axis minor, unpadded).  A sparse layer GATHERS a turn of
    64 listed blocks a row and key-value head out of the stacked leaf where
    it lies (the leaf bitcast to blocks of 64 positions, the layer part of
    the gather's index: six gathers of ``[8, 2, 64, 64, 128]``, keys and
    values of three layers) and the 31 keys before ``pos`` for the window a
    position completes (three of ``[8, 2, 31, 128]``); nothing slices a
    layer out of ``k`` / ``v`` first.  The top rung's temporaries
    (3.2 GB: a tile of the selection's float32 scores, the MLP's gate and
    up, the scan's chunks) fit beside the arguments."""
    from ray_tpu.llm.engine import jit_prefill_one

    step, cache, params = cell_decode_step("minicpm_sala_l12")
    memory = step.memory_analysis()
    assert cache["k"].shape == cache["v"].shape == (3, 8, 2, 16384, 128)
    assert cache["kbar"].shape == (3, 8, 2, 1024, 128)
    assert cache["state"].shape == (9, 8, 32, 128, 128)
    assert 8.41e9 < memory.argument_size_in_bytes < 8.44e9
    assert 0.56e9 < memory.alias_size_in_bytes < 0.57e9  # the whole cache
    assert memory.temp_size_in_bytes < 0.05e9  # 0.008 GB
    assert memory.generated_code_size_in_bytes < 15e6  # 8 MB
    text = step.as_text()
    shape = ",".join(map(str, cache["state"].shape))
    kernels = mamba_kernels(text, shape)
    assert len(kernels) == 9 == text.count("tpu_custom_call")
    assert not [name for name in kernels if "remat" in name]
    assert leaf_is_only_handed_on(text, shape)
    for leaf in ("k", "kbar"):  # positions (windows) on the sublanes
        dims = ",".join(map(str, cache[leaf].shape))
        assert re.search(
            re.escape(f"bf16[{dims}]{{4,3,2,1,0:T(8,128)(2,1)}}"), text)
    gathers = re.findall(r"= (\w+\[[\d,]*\])\S* gather\(", text)
    assert gathers.count("bf16[8,2,64,64,128]") == 6
    assert gathers.count("bf16[8,2,31,128]") == 3
    # a layer's slice of the keys or the values is never made (of the
    # pooled keys it is, inside the fusion that scores them: 4 MB a layer)
    assert not re.findall(
        r"= bf16\[(?:1,)?8,2,16384,128\]\S* "
        r"(?:copy|slice|dynamic-slice|fusion)\(", text)
    fam, cfg, _, _ = cell("minicpm_sala_l12")
    formats = step.input_formats[0][0]
    lying = jax.tree.map(lambda leaf, fmt: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=fmt), params, formats)
    tokens = on_chip(jax.ShapeDtypeStruct((16384,), jnp.int32))
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    rung = jit_prefill_one(fam, cfg).lower(
        lying, cache, tokens, scalar, scalar).compile()
    assert rung.input_formats[0][0] == formats
    assert rung.memory_analysis().alias_size_in_bytes > 0.56e9
    assert rung.memory_analysis().temp_size_in_bytes < 3.6e9  # 3.23 GB
    assert rung.memory_analysis().generated_code_size_in_bytes < 30e6
    assert (memory.argument_size_in_bytes
            + rung.memory_analysis().temp_size_in_bytes) < 12.5e9


@pytest.mark.parametrize("layers,at", [(1, 0), (9, 4)],
                         ids=["one_layer", "layer_4_of_the_stack"])
def test_delta_update_alone_is_one_kernel_over_the_donated_leaf(
    on_chip, as_if_on_tpu, layers, at
):
    """``ops.delta_update`` at the published widths and the cell's 64 slots,
    on its own: a stack of one (what ``olmo_hybrid_decode.delta_step`` makes
    of one layer's state, as ``benchmarks/olmo_hybrid_all_layers.py
    --time-delta`` donates it) and layer 4 of the cell's nine.  ONE custom
    call from the donated leaf to itself, no temporary, no copy of the
    state, the small operands a few megabytes beside it."""
    slots, heads, dk, dv = 64, 30, 96, 192
    leaf, q, v, scalar = (
        on_chip(jax.ShapeDtypeStruct(dims, jnp.float32)) for dims in (
            (layers, slots, heads // 2, dk, 2 * dv), (slots, heads, dk),
            (slots, heads, dv), (slots, heads, 1)))
    step = jax.jit(
        lambda leaf, *small: delta_update.delta_update(leaf, at, *small),
        donate_argnums=(0,)).lower(leaf, q, q, v, scalar, scalar).compile()
    memory = step.memory_analysis()
    state = layers * slots * heads * dk * dv * 4
    assert memory.alias_size_in_bytes == state
    assert memory.temp_size_in_bytes == 0
    assert memory.argument_size_in_bytes < state + 8e6
    text = step.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "output_to_operand_aliasing={{0}: (1, {})}" in text
    shape = f"[{layers},{slots},{heads // 2},{dk},{2 * dv}]"
    assert set(re.findall(  # the kernel's own result is a tuple
        rf"= f32{re.escape(shape)}\S* ([\w-]+)\(", text)) == {
            "parameter", "get-tuple-element"}


def test_a_steps_linear_layers_are_calls_of_one_lowered_kernel(
    on_chip, as_if_on_tpu
):
    """The layer is the kernel's prefetched operand, not a constant of its
    index maps, and ``delta_update._call`` a jitted function: three layers
    lower to ONE ``tpu_custom_call`` called three times (a kernel's fifteen
    unrolled rows are ~0.2 s of tracing and lowering, which nine constants
    paid nine times at every start of a replica) and compile to three, each
    over the leaf where it lies."""
    leaf, q, v, scalar = (
        on_chip(jax.ShapeDtypeStruct(dims, jnp.float32)) for dims in (
            (3, 8, 15, 96, 384), (8, 30, 96), (8, 30, 192), (8, 30, 1)))

    def three(leaf, *small):
        outs = []
        for at in range(3):
            o, leaf = delta_update.delta_update(leaf, at, *small)
            outs.append(o)
        return outs, leaf

    lowered = jax.jit(three, donate_argnums=(0,)).lower(
        leaf, q, q, v, scalar, scalar)
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_kimi_decode_step_updates_three_leaves_where_they_lie(
    cell, cell_decode_step, on_chip
):
    """The Kimi-Linear cell's programs at its size (published widths, 21
    layers ``k`` + ``MKKK`` x 5, 16 of 256 experts held, an eighth of the
    vocabulary, 64 slots x 4096): the decode step's arguments are the
    weights (6.73 GB) and a cache of 2.15 GB of KDA state (``f32[16,64,32,
    128,128]``: a head's ``[128, 128]`` is whole tiles, nothing packed or
    padded), 0.15 GB of convolution windows and 1.51 GB of latents, ALL of
    it aliased to the output.  Each KDA layer is ONE Pallas kernel
    (``ops/delta_update.py`` with the decay a column beside ``q`` and ``k``)
    whose operand and result are the WHOLE donated leaf, aliased: sixteen
    calls of one lowered kernel, and nothing else produces an array of the
    leaf's or of a layer's shape.  The five latent layers read their
    slices of the stacked cache in blocks through the family's second kind
    of kernel (``ops/latent_attention.py``: five calls of one lowered kernel
    over the donated ``latent`` leaf where it lies, no loop and no block of
    latents made by anything else), and the top rungs
    the engine compiles (2048 is the highest the cell's prompts reach, 4096
    the highest it serves) are three scanned layer bodies whose
    temporaries fit beside the step's arguments on the chip."""
    from ray_tpu.llm.engine import jit_prefill_one

    step, cache, params = cell_decode_step("kimi_linear_l21_ep16")
    memory = step.memory_analysis()
    assert cache["state"].shape == (16, 64, 32, 128, 128)
    assert cache["conv"].shape == (16, 64, 3 * 12288)
    assert cache["latent"].shape == (5, 64, 4096, 576)
    assert 10.5e9 < memory.argument_size_in_bytes < 10.6e9
    assert 3.80e9 < memory.alias_size_in_bytes < 3.82e9  # the whole cache
    assert memory.temp_size_in_bytes < 0.1e9  # 0.07 GB
    assert memory.generated_code_size_in_bytes < 60e6
    text = step.as_text()
    shape = ",".join(map(str, cache["state"].shape))
    leaf = re.escape(f"f32[{shape}]{{4,3,2,1,0:T(8,128)}}")
    assert re.search(leaf, text)
    kernels = re.findall(
        rf"^\s*%(\S+) = \({leaf}, [^\n]*?\) custom-call\(%constant[\w.]*, "
        r'%([\w.-]+), [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        + re.escape("output_to_operand_aliasing={{0}: (1, {})}"), text, re.M)
    assert len(kernels) == 16
    assert len(latent_kernels(text, cache["latent"])) == 5
    assert text.count("tpu_custom_call") == 16 + 5
    producers = dict(re.findall(
        rf"^\s*(?:ROOT )?%(\S+) = f32\[{shape}\]\S* ([\w-]+)\(", text, re.M))
    assert set(producers.values()) == {"parameter", "get-tuple-element"}
    assert {operand for _, operand in kernels} <= set(producers)
    assert "64,32,128,128]" not in text.replace(f"[{shape}]", "")
    made, slices, clones = window_traffic(text, cache["conv"])
    assert not clones and made == ["fusion"] * 16
    assert not re.search(r' while\(.*op_name="[^"]*kimi\.mla/while"', text)
    assert not re.search(r"bf16\[[\d,]*\b512,576\]", text)
    fam, cfg, _, _ = cell("kimi_linear_l21_ep16")
    formats = step.input_formats[0][0]
    lying = jax.tree.map(lambda leaf, fmt: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=fmt), params, formats)
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    for top, limit in ((2048, 0.55e9), (4096, 1.1e9)):
        tokens = on_chip(jax.ShapeDtypeStruct((top,), jnp.int32))
        rung = jit_prefill_one(fam, cfg).lower(
            lying, cache, tokens, scalar, scalar).compile()
        assert rung.input_formats[0][0] == formats
        assert rung.memory_analysis().temp_size_in_bytes < limit
        assert rung.memory_analysis().generated_code_size_in_bytes < 30e6
        # the chunk's (I + A)^-1 once a KDA body: layer 1's and the run's
        assert len(re.findall(
            'custom_call_target="InvertDiagBlocksLowerTriangular"',
            rung.as_text())) == 2
        # 15.75 GB of the chip: arguments + the rung's temporaries
        assert (memory.argument_size_in_bytes
                + rung.memory_analysis().temp_size_in_bytes) < 12.0e9


@pytest.mark.parametrize("layers,at", [(1, 0), (16, 7)],
                         ids=["one_layer", "layer_7_of_the_stack"])
def test_vector_gate_delta_update_is_one_kernel_over_the_donated_leaf(
    on_chip, as_if_on_tpu, layers, at
):
    """``ops.delta_update`` with the decay a vector a head, at Kimi-Linear's
    widths and the cell's 64 slots, on its own: ONE custom call from the
    donated leaf to itself, no temporary, no copy of the state, the small
    operands (the decay's columns among them) a few megabytes beside it."""
    slots, heads, dk = 64, 32, 128
    leaf, q, scalar = (
        on_chip(jax.ShapeDtypeStruct(dims, jnp.float32)) for dims in (
            (layers, slots, heads, dk, dk), (slots, heads, dk),
            (slots, heads, 1)))
    step = jax.jit(
        lambda leaf, *small: delta_update.delta_update(leaf, at, *small),
        donate_argnums=(0,)).lower(leaf, q, q, q, q, scalar).compile()
    memory = step.memory_analysis()
    state = layers * slots * heads * dk * dk * 4
    assert memory.alias_size_in_bytes == state
    assert memory.temp_size_in_bytes == 0
    assert memory.argument_size_in_bytes < state + 8e6
    text = step.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "output_to_operand_aliasing={{0}: (1, {})}" in text


# (A, B, T, H, C, rkv): the three cells that hold a latent cache
LATENT_LEAVES = {"mistral4": (9, 32, 16384, 32, 320, 256),
                 "kimi_linear": (5, 64, 4096, 32, 576, 512),
                 "longcat": (8, 32, 2048, 64, 576, 512)}


@pytest.mark.parametrize("family", LATENT_LEAVES)
def test_latent_attention_alone_is_one_kernel_over_the_leaf_where_it_lies(
    on_chip, as_if_on_tpu, family
):
    """``ops.latent_attention`` at the three served shapes, on its own, a
    layer inside the stack: ONE custom call that reads the leaf as the
    program holds it (positions minor; ``latent_kernels`` checks the view is
    a bitcast), no temporary, and the fast memory it asks for (a cell's block
    of latents twice and as much again) under a quarter of the v5e's 128
    MiB."""
    from ray_tpu.ops import latent_attention as la

    a, b, t, h, c, rkv = LATENT_LEAVES[family]
    leaf, qc, own = (on_chip(jax.ShapeDtypeStruct(dims, jnp.bfloat16))
                     for dims in ((a, b, t, c), (b, h, c), (b, c)))
    pos = on_chip(jax.ShapeDtypeStruct((b,), jnp.int32))
    step = jax.jit(lambda leaf, qc, own, pos: la.latent_attention(
        leaf, a - 2, qc, own, pos, rkv=rkv, scale=0.1)).lower(
            leaf, qc, own, pos).compile()
    assert step.memory_analysis().temp_size_in_bytes == 0
    text = step.as_text()
    assert len(latent_kernels(text, leaf)) == 1 == text.count(
        "tpu_custom_call")
    assert " while(" not in text
    asked = 4 * la.slots_per_cell(b, c, 512, 2) * c * 512 * 2
    assert asked <= 4 * la._BLOCK_BYTES < 32 << 20


MAMBA_WIDTHS = {"granite_64_heads_1_group": (64, 1),
                "nemotron_128_heads_8_groups": (128, 8)}


def mamba_operands(on_chip, layers, slots, heads, groups, p=64, n=128):
    """The leaf and the small operands of ``ops.mamba_update``: leaf, x, dt,
    keep, b, c."""
    return [on_chip(jax.ShapeDtypeStruct(dims, jnp.float32)) for dims in (
        (layers, slots, heads, p, n), (slots, heads, p), (slots, heads),
        (slots, heads), (slots, groups, n), (slots, groups, n))]


@pytest.mark.parametrize("layers,at", [(1, 0), (5, 3)],
                         ids=["one_layer", "layer_3_of_the_stack"])
@pytest.mark.parametrize("widths", MAMBA_WIDTHS)
def test_mamba_update_alone_is_one_kernel_over_the_donated_leaf(
    on_chip, as_if_on_tpu, widths, layers, at
):
    """``ops.mamba_update`` at both families' published widths and the
    cells' 64 slots, on its own: a stack of one and a layer inside a stack.
    ONE custom call from the donated leaf to itself, no temporary, no copy
    of the state, the small operands (``dt x`` a head's width, ``b`` and
    ``c`` a GROUP, never a head) a few megabytes beside it."""
    heads, groups = MAMBA_WIDTHS[widths]
    slots = 64
    step = jax.jit(
        lambda leaf, *small: mamba_update.mamba_update(leaf, at, *small),
        donate_argnums=(0,)).lower(
            *mamba_operands(on_chip, layers, slots, heads, groups)).compile()
    memory = step.memory_analysis()
    state = layers * slots * heads * 64 * 128 * 4
    assert memory.alias_size_in_bytes == state
    assert memory.temp_size_in_bytes == 0
    assert memory.argument_size_in_bytes < state + 8e6
    text = step.as_text()
    shape = f"{layers},{slots},{heads},64,128"
    assert len(mamba_kernels(text, shape)) == 1 == text.count(
        "tpu_custom_call")
    assert leaf_is_only_handed_on(text, shape)


def test_a_steps_mamba_layers_are_calls_of_one_lowered_kernel(
    on_chip, as_if_on_tpu
):
    """The layer is the kernel's prefetched operand, not a constant of its
    index maps, and ``mamba_update._call`` a jitted function: three layers
    lower to ONE ``tpu_custom_call`` called three times (Granite's step has
    thirty-six: a constant a layer would trace and lower the kernel
    thirty-six times at every start of a replica) and compile to three,
    each over the leaf where it lies."""
    def three(leaf, *small):
        outs = []
        for at in range(3):
            y, leaf = mamba_update.mamba_update(leaf, at, *small)
            outs.append(y)
        return outs, leaf

    lowered = jax.jit(three, donate_argnums=(0,)).lower(
        *mamba_operands(on_chip, 3, 8, 64, 1))
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# What hands an array on as it is, and what prefetches one into the chip's
# fast memory (``S(1)``), whole or in pieces that are then viewed as one.
VIEWS = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
         "call", "conditional", "opt-barrier"}
PREFETCHES = {"copy-done", "slice-done", "ConcatBitcast"}
HLO_DTYPES = {"bfloat16": "bf16", "float32": "f32"}


def named_instructions(text, entry_only=False):
    """(name, result, op, the rest of the line) of every instruction of a
    compiled program's text that is no view, in its ENTRY computation or in
    every computation that is not a fusion's own (loops' bodies and branches
    included)."""
    fused = set(re.findall(r"calls=%([\w.-]+)", text))
    for computation in re.finditer(
            r"^(ENTRY )?%([\w.-]+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S):
        is_entry, name, body = computation.groups()
        if (entry_only and not is_entry) or name in fused:
            continue
        for name, result, op, rest in re.findall(
                r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\((.*)$",
                body, re.M):
            if op not in VIEWS:
                yield name, result, op, rest


def instructions(text, entry_only=False):
    """``named_instructions`` without the names."""
    for _name, *rest in named_instructions(text, entry_only):
        yield tuple(rest)


def weight_parts(params):
    """(HLO dtype, dims without the 1s) of every part of a weight leaf that
    a program might take out whole and that is >= 8 MB: the leaf, one layer
    of a stack, one sublayer or expert of a layer (each suffix of the
    leaf's shape)."""
    parts = set()
    for leaf in jax.tree.leaves(params):
        for i in range(leaf.ndim):
            if math.prod(leaf.shape[i:]) * leaf.dtype.itemsize >= 8e6:
                parts.add((HLO_DTYPES[leaf.dtype.name],
                           tuple(d for d in leaf.shape[i:] if d != 1)))
    return parts


def copied_weights(text, params, entry_only=True):
    """[(op, array)] of the instructions of a compiled program (its ENTRY
    computation, or every computation that is not a fusion's) that PRODUCE
    an array of a weight part's dtype and dims (``weight_parts``) and are
    neither views nor prefetches under 100 MB: the copies of weights the
    program makes every time it runs.  Any op counts, not ``copy`` and
    ``transpose`` alone: the v5e's relayout of Mistral's ``wq`` was a
    multi-output ``fusion`` named for nothing (``fusion.1230``, 16 outputs),
    whose only trace beyond itself was a prefetch of each output."""
    parts = weight_parts(params)
    found = []
    for result, op, rest in instructions(text, entry_only):
        if op.endswith("-start"):
            continue
        if op == "custom-call":
            op = re.search(r'custom_call_target="(\w+)"', rest).group(1)
        for dtype, dims, layout in re.findall(
                r"(\w+)\[([\d,]*)\](?:\{([^}]*)\})?", result):
            dims = [int(d) for d in dims.split(",") if d]
            part = (dtype, tuple(d for d in dims if d != 1))
            prefetch = (op in PREFETCHES and "S(1)" in layout
                        and math.prod(dims) * 2 < 100e6)
            if part in parts and not prefetch:
                found.append((op, f"{dtype}{dims}{{{layout}}}"))
    return found


@pytest.mark.parametrize("name", CELL_CONFIGS)
def test_decode_step_copies_no_weight(cell_decode_step, name):
    """The decode step reads its weights where they lie: with their device
    layouts left to the compiler (``jit_decode_step``) nothing in the ENTRY
    computation produces an array the size of a weight, a layer's or an
    expert's part of one, but a prefetch into the chip's fast memory.  In
    the DEFAULT layout (``[L, E, H, D]`` row-major) the Mistral step relaid
    ``wq`` / ``wk`` / ``wv`` of every layer before their products, every
    step: 48 fusion outputs, 0.81 GB written and read back, 2.3 of its 15.6
    ms on the chip (PERF.md, PR 44); the compiler asks for ``[L, H, E, D]``
    (``major_to_minor=(0, 2, 1, 3)``) and reads that in place."""
    compiled, _, params = cell_decode_step(name)
    copies = copied_weights(compiled.as_text(), params)
    if name == "longcat_flash_l4_ep32":
        # Today's truth (ROADMAP S5 keeps it): each attention's ``wkv_b``
        # still leaves its ``[4, 2, 512, 64, 256]`` stack by ONE sliced copy
        # (a ``slice_bitcast_fusion`` of eight outputs, 0.134 GB a step);
        # the second copy each then took, into ``{2,0,1}`` for the absorbed
        # products (eight ``copy_bitcast_fusion``), is what the layout cured.
        assert len(copies) <= 8
        assert {array.split("{")[0] for _, array in copies} <= {
            "bf16[512, 64, 256]"}
    else:
        assert not copies
    if name == "mistral7b_l16":
        blocks = compiled.input_formats[0][0]["blocks"]
        assert {k: blocks[k].layout.major_to_minor for k in blocks} == dict(
            dict.fromkeys(("wq", "wk", "wv"), (0, 2, 1, 3)),
            wo=(0, 1, 2, 3), rms1=(0, 1), rms2=(0, 1),
            w_gate=(0, 1, 2), w_up=(0, 1, 2), w_down=(0, 1, 2))


@pytest.fixture(scope="module")
def mistral_rung(cell, cell_decode_step, on_chip):
    """``compiled(rung, as_they_lie=True)``: a rung of the Mistral cell's
    ladder as the engine compiles it (``jit_prefill_one`` against the weights
    as the decode step has them laid), or against the default layouts; ~3.5 s
    each."""
    from ray_tpu.llm.engine import jit_prefill_one

    fam, cfg, params, cache = cell("mistral7b_l16")
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))

    @functools.cache
    def compiled(rung, as_they_lie=True):
        weights = params
        if as_they_lie:
            formats = cell_decode_step("mistral7b_l16")[0].input_formats[0][0]
            weights = jax.tree.map(lambda leaf, fmt: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=fmt), params, formats)
        tokens = on_chip(jax.ShapeDtypeStruct((rung,), jnp.int32))
        return jit_prefill_one(fam, cfg).lower(
            weights, cache, tokens, scalar, scalar).compile()

    return compiled


@pytest.mark.parametrize("rung", [256, 512, 1024, 2048])
def test_prefill_rung_copies_no_more_weights_in_the_decode_steps_layouts(
    cell, cell_decode_step, mistral_rung, rung
):
    """Decode's choice of the weights' layouts is prefill's too (the engine
    compiles every rung, ``jit_prefill_one``, against the weights as the
    decode step has them laid).  Per rung of the Mistral cell's ladder:
    compiled so, the rung takes the formats it is given (it relays nothing
    on its way in) and holds no more weight-sized copies, in its ENTRY and
    in its scan's body, than compiled against the default layouts: one
    fewer (4 against 5)."""
    params = cell("mistral7b_l16")[2]
    formats = cell_decode_step("mistral7b_l16")[0].input_formats[0][0]

    def copies(rung):
        return copied_weights(rung.as_text(), params, entry_only=False)

    as_they_lie = mistral_rung(rung)
    assert as_they_lie.input_formats[0][0] == formats
    assert len(copies(as_they_lie)) <= len(
        copies(mistral_rung(rung, as_they_lie=False)))


def test_the_dense_familys_top_rung_scores_in_tiles(mistral_rung):
    """The Mistral cell's 2048 rung through ``layers.blocked_attention``:
    no array with two axes of the rung's length (the ``[1, 32, 2048, 2048]``
    float32 scores, 537 MB a layer, that the dense form wrote, masked and
    normalised in HBM; the compiler printed them ``f32[32,2048,2048]``), and
    temporaries of 0.25 GB where that form reserved 1.36 GB, which was the
    Mistral cells' ``memory_peak_bytes`` over their live buffers (PERF.md,
    PR 66)."""
    rung = mistral_rung(2048)
    squares = [array for array, *_ in instructions(rung.as_text())
               if re.search(r"\b2048,(\d+,)*2048\b", array.replace(" ", ""))]
    assert not squares
    assert rung.memory_analysis().temp_size_in_bytes < 0.4e9  # 0.253 GB


# Cache leaves as the families shape them (positions on the axis before the
# last), and neighbours of theirs: (shape, dtype).
CACHE_LEAVES = [
    ((16, 16, 8, 2048, 128), "bfloat16"),  # Mistral cell
    ((8, 32, 2048, 576), "bfloat16"),      # LongCat cell
    ((12, 32, 12, 1024, 64), "bfloat16"),  # GPT-2
    ((12, 32, 12, 1024, 64), "float32"),
    ((22, 32, 4, 2048, 64), "bfloat16"),   # TinyLlama
    ((8, 32, 2048, 640), "bfloat16"),
    ((8, 32, 2000, 576), "bfloat16"),
    ((8, 32, 2048, 96), "float32"),
    ((8, 16, 8, 2048, 256), "float32"),
    ((2, 64, 4, 4096, 192), "bfloat16"),   # MiMo cell: full keys,
    ((5, 64, 8, 128, 192), "bfloat16"),    # a ring of keys (one tile),
    ((2, 64, 4, 4096, 128), "bfloat16"),   # values
    ((5, 64, 8, 128, 128), "bfloat16"),
    ((9, 32, 16384, 320), "bfloat16"),     # Mistral-4 cell: a last axis that
    ((9, 1, 16384, 320), "bfloat16"),      # is no multiple of 128, and its
    ((9, 1, 8192, 320), "bfloat16"),       # one-row twin at each rung
    ((9, 1, 4096, 320), "bfloat16"),
    ((9, 1, 2048, 320), "bfloat16"),
    ((9, 1, 1024, 320), "bfloat16"),
    ((9, 1, 512, 320), "bfloat16"),
    ((9, 1, 256, 320), "bfloat16"),
    ((3, 32, 8, 16384, 128), "bfloat16"),  # Laguna cell: full keys / values
    ((6, 32, 8, 512, 128), "bfloat16"),    # and a ring of four lane tiles'
    ((6, 1, 8, 512, 128), "bfloat16"),     # worth of positions (on the
    ((3, 1, 8, 16384, 128), "bfloat16"),   # sublanes here: 32 tiles of 16);
    ((3, 1, 8, 8192, 128), "bfloat16"),    # the one-row twins at each rung
    ((3, 1, 8, 4096, 128), "bfloat16"),
    ((3, 1, 8, 2048, 128), "bfloat16"),
    ((3, 1, 8, 1024, 128), "bfloat16"),
    ((3, 1, 8, 512, 128), "bfloat16"),
    ((3, 1, 8, 256, 128), "bfloat16"),
    ((3, 8, 2, 16384, 128), "bfloat16"),   # MiniCPM-SALA cell: keys / values
    ((3, 8, 2, 1024, 128), "bfloat16"),    # of two heads and their POOLED
    ((3, 1, 2, 1024, 128), "bfloat16"),    # keys, a window every 16
    ((3, 1, 2, 16, 128), "bfloat16"),      # positions; one-row twins at the
    ((3, 1, 2, 256, 128), "bfloat16"),     # top and the lowest rung
]


@pytest.mark.parametrize("shape,dtype", CACHE_LEAVES, ids=str)
def test_tile_positions_follows_the_layout_the_tpu_gives(
    one_chip, shape, dtype
):
    """``write_token_to_cache`` sizes its tile from where the TPU lays the
    position axis, which it cannot ask: ``tile_positions`` restates the
    compiler's rule (last axis minor unless it is no multiple of 128), and
    this reads the rule back from the compiler.  A tile of the wrong size
    stays correct and costs a LongCat step 1.2 ms (PERF.md, PR 34)."""
    leaf = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    text = jax.jit(lambda a: a + 1).lower(leaf).compile().as_text()
    minor_to_major = re.search(
        r"entry_computation_layout=\{\(\w+\[[\d,]+\]\{([\d,]+):", text)
    lanes, sublanes = map(int, minor_to_major.group(1).split(",")[:2])
    axis = len(shape) - 2
    assert axis in (lanes, sublanes)
    want = 128 if axis == lanes else 32 // jnp.dtype(dtype).itemsize
    assert tile_positions(shape, dtype, axis) == want
