"""Ask the chip's compiler, without the chip.

libtpu is installed here and compiles for a device that is DESCRIBED and
not attached (``jax.experimental.topologies``), so the Mosaic kernels of
the main path are compiled at real widths for a TPU v5e on every tier-1
run. Interpret mode cannot see what this sees: ``paged_decode_attention_
kernel`` passed every interpret-mode test while the v5e compiler refused
it at every 64-wide head shape the repo ships (an in-kernel reshape of a
64-lane minor dim). A compile that passes is not a chip run — results and
times come from ``chip_smoke.py`` and ``tests_tpu/`` — but a compile that
FAILS here would fail there, and costs no chip time.

Nothing runs, so arguments are shapes; and the kernels are called with
``interpret=False`` directly, because code that asks
``jax.default_backend()`` still sees the CPU here.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import functools  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from pddl_tpu.ops.attention import (  # noqa: E402
    flash_attention,
    paged_decode_attention_kernel,
)


@pytest.fixture(scope="module")
def v5e_chip():
    """One described v5e device as a sharding, with the persistent compile
    cache off around the module: an entry written for a described device
    cannot be read back without one, and warns on every later run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it cannot describe v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shapes_on(chip, *args):
    """``args`` (arrays, numpy scalars, shapes; any pytree) as shapes
    placed on the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype if hasattr(x, "dtype")
            else np.asarray(x).dtype, sharding=chip), args)


def _mosaic_calls(text):
    """The Mosaic kernel calls of a compiled module's text."""
    return [line.strip()[:160] for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _kernels_by_name(text):
    """The Mosaic kernel calls of a compiled module, by kernel: the paged
    attention kernel's instructions are named after their scope
    (``attn_window``, ``attn_global``, ``attn_latent``), the expert
    kernel's after its ``jit`` (``_expert_ffn_call``). Each maps its
    instruction name to the ``op_name`` the benchmark's scope table
    reads (`serve_paged_moe_lm._INSTR`)."""
    from chipbench.systems.serve_paged_moe_lm import _INSTR

    found = {"attention": {}, "expert": {}, "other": {}}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, op_name = _INSTR.match(line).groups()
        kind = ("attention" if name.startswith("attn_") else "expert"
                if name.startswith("_expert_ffn_call") else "other")
        found[kind][name] = op_name
    return found


def _assert_expert_kernels(text, routed_layers):
    """One expert kernel a routed layer, under the scope ``moe_ffn`` as
    the scope table reads it, no other kernel but attention's, and no
    loop left under ``moe_ffn``."""
    from chipbench.systems.serve_paged_moe_lm import scope_of

    kernels = _kernels_by_name(text)
    module = re.match(r"HloModule (\S+?),", text).group(1)
    assert len(kernels["expert"]) == routed_layers, kernels["expert"]
    assert {scope_of(module, op) for op in kernels["expert"].values()} \
        == {"moe_ffn"}, kernels["expert"]
    assert not kernels["other"], kernels["other"]
    assert "/moe_ffn/while" not in text
    return kernels


def _makes(text, shape):
    """The instructions of a compiled module whose RESULT has ``shape``
    (``bf16[3073,20,16,128]``), parameters apart: a pool-shaped one is a
    copy of the pool, whatever its name."""
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= \(?{re.escape(shape)}", line)
            and " parameter(" not in line]


def _compile_with_kernel(fn, *shapes, kernel=True):
    """Compile for the described chip; the Mosaic kernel must be in it
    (or, ``kernel=False``, the documented jnp fallback must be)."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert ("tpu_custom_call" in text) == kernel


@pytest.mark.parametrize("heads,kv_heads,head_dim,block_size,context,window", [
    (12, 12, 64, 8, 1024, None),    # GPT-small, the engine's default block
    (12, 12, 64, 16, 1024, None),
    (12, 4, 64, 8, 1024, None),     # Llama-small (GQA)
    (12, 4, 64, 16, 1024, None),
    (16, 16, 128, 8, 1024, None),   # 128-wide heads: a 256-lane fused leaf
    (16, 16, 128, 16, 1024, None),
    (20, 20, 64, 16, 1024, None),   # GPT-2-large, the benchmark's configuration
    (28, 4, 128, 16, 16384, None),  # SmallThinker: 7 q heads a kv head over
    (28, 4, 128, 16, 16384, 4096),  # a 1,024-wide table, NoPE-global and window
    (20, 1, 576, 16, 20480, None),  # GLM-4.7-Flash's latent leaf: 20 absorbed
                                    # q heads over ONE 640-lane cache head
])
def test_paged_decode_kernel_compiles_for_v5e(v5e_chip, heads, kv_heads,
                                              head_dim, block_size, context,
                                              window):
    """Eight slots over the context, bf16, over the fused pool leaf
    ``[N, Hkv, bs, 2D]``, as the engine's paged tick calls it: ONE
    Mosaic call for the whole sweep (the slots' loops are inside it) that
    takes the leaf where it lies — no instruction makes or copies an
    array of the leaf's shape."""
    slots, table = 8, context // block_size

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    latent = kv_heads == 1 and head_dim == 576
    # The latent entry is key in all of its 576 values and value in the
    # first 512, stored in 640 lanes (a 576-lane leaf is refused: Mosaic
    # copies whole 128-lane tiles).
    lanes, value_lanes = (640, (0, 512)) if latent else (2 * head_dim, None)
    pool = sds((slots * table + 1, kv_heads, block_size, lanes),
               jnp.bfloat16)
    text = jax.jit(
        lambda q, kv, t, i: paged_decode_attention_kernel(
            q, kv, t, i, window=window, interpret=False,
            value_lanes=value_lanes)).lower(
        sds((slots, heads, 1, head_dim), jnp.bfloat16), pool,
        sds((slots, table), jnp.int32), sds((slots,), jnp.int32)
    ).compile().as_text()
    assert len(_mosaic_calls(text)) == 1
    assert not _makes(text, "bf16[" + ",".join(map(str, pool.shape)) + "]")


# ----------------------------------------------- the engine's own programs
_POOL_BLOCKS, _SLOTS, _BLOCK = 3073, 48, 16   # the benchmark cell's pool


def _paged_engine_for_v5e(family):
    """A ``ServeEngine(paged=True)`` at the benchmark cell's pool shapes
    (48 slots, block 16, 3073 blocks, 1024 positions, bf16), two layers
    of 1280: GPT-2-large's 20x64 heads, or a GQA 12/4x64 Llama."""
    from pddl_tpu.models.gpt import GPT
    from pddl_tpu.models.llama import Llama
    from pddl_tpu.serve import ServeEngine

    kw = dict(vocab_size=512, max_len=1024, depth=2, dtype=jnp.bfloat16,
              param_dtype=jnp.bfloat16)
    if family == "gpt_20x64":
        model = GPT(embed_dim=1280, num_heads=20, **kw)
    else:
        model = Llama(embed_dim=768, num_heads=12, num_kv_heads=4,
                      intermediate_dim=2048, **kw)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32),
                        train=False)["params"]
    return ServeEngine(model, {"params": params}, paged=True,
                       max_slots=_SLOTS, prefill_len=768,
                       prefix_block_size=_BLOCK,
                       prefix_cache_blocks=_POOL_BLOCKS)


@pytest.mark.parametrize("family", ["gpt_20x64", "llama_gqa_12_4x64"])
def test_paged_programs_keep_the_pool_in_place_on_v5e(v5e_chip, family,
                                                      monkeypatch):
    """The engine's own ``_tick_paged``, ``_chunk_paged`` and
    ``_chunk_paged_wide``, compiled for the described chip with the pool
    donated and no layout passed anywhere: the compiler must find
    nothing to relayout. The parent of PR 29 fails every assertion
    below on its 64-wide K and V leaves (three pool-sized copies a leaf
    in each program, 75 % of the chip's time in both benchmark cells).

    The engine asks ``jax.default_backend()`` whether to use the Mosaic
    kernel; here that is the CPU, so the test answers for the chip."""
    eng = _paged_engine_for_v5e(family)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    shapes = functools.partial(_shapes_on, v5e_chip)

    def chunk_args(width):
        return shapes(*eng._chunk_args_paged(width))

    programs = {
        "_tick_paged": (eng._tick_p, shapes(*eng._tick_args())),
        "_chunk_paged": (eng._chunk_p, chunk_args(eng._chunk)),
        "_chunk_paged_wide": (eng._chunk_wide_p,
                              chunk_args(eng.prefill_len)),
    }
    leaves = [leaf for leaf in jax.tree.leaves(eng._cache) if leaf.ndim == 4]
    assert len(leaves) == 2                     # one fused leaf a layer
    pool_shape = f"bf16[{','.join(map(str, leaves[0].shape))}]"
    leaf_bytes = leaves[0].size * 2
    logical = leaf_bytes * len(leaves)
    for name, (prog, args) in programs.items():
        compiled = prog.lower(*args).compile()
        text = compiled.as_text()
        header = text.split("\n", 1)[0]
        assert f"jit_{name}" in header
        # (1) No op copies or rematerialises a pool-sized array, and no
        # temporary of any name is as large as one leaf.
        shape = r"\(?" + re.escape(pool_shape)
        moved = [
            line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= {shape}\S* (copy|copy-start|copy-done)\(", line)
            or re.search(rf"%\S*remat\S* = {shape}", line)]
        assert not moved, f"{name}: pool-sized copies\n" + "\n".join(moved)
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < leaf_bytes, name
        # (2) Every pool leaf is donated in place.
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry",
                            header).group(1)
        entry = re.search(
            r"entry_computation_layout=\{\((.*)\)->\((.*)\)\}", header)
        array = r"\w+\[[\d,]*\]\{[^}]*\}"     # shape{layout}, in order
        params = re.findall(array, entry.group(1))
        results = re.findall(array, entry.group(2))
        pool_params = [i for i, p in enumerate(params)
                       if p.startswith(pool_shape)]
        assert len(pool_params) == len(leaves)
        aliased = {int(m) for m in re.findall(r"\((\d+), \{\}, ", aliases)}
        assert set(pool_params) <= aliased, (name, aliases)
        # (3) In and out in one layout, the shape's default: row-major,
        # which nothing in the engine asks for.
        pool_layouts = ({params[i] for i in pool_params}
                        | {r for r in results if r.startswith(pool_shape)})
        assert len(pool_layouts) == 1, pool_layouts
        assert pool_layouts.pop().startswith(pool_shape + "{3,2,1,0:T(8,128)")
        # (4) The Mosaic kernel serves the tick, ONE call a layer (each
        # slot's walk over its blocks is inside it); chunks take the jnp
        # path.
        assert len(_mosaic_calls(text)) == \
            (len(leaves) if name == "_tick_paged" else 0)
        # (5) No padding: what the program holds of the pool is what the
        # pool's data weighs (a 64-lane leaf pinned row-major is 2x).
        held = memory.argument_size_in_bytes
        others = sum(
            np.prod(a.shape, dtype=np.int64) * a.dtype.itemsize
            for a in jax.tree.leaves(args) if a.ndim != 4)
        assert held - others <= 1.05 * logical, (name, held, others, logical)
        assert held - others >= 0.95 * logical


def _vocab_wide_gathers_and_sorts(text, rows, vocab):
    """The ``gather`` instructions of a compiled module that yield
    ``rows x vocab`` elements, and its ``sort`` instructions over
    ``[rows, vocab]`` operands (fused computations included: their
    bodies are in the text)."""
    gathers, sorts = [], []
    for line in text.splitlines():
        made = re.search(r"= \w+\[([\d,]*)\]\S* gather\(", line)
        if made and np.prod([int(d) for d in made.group(1).split(",") if d],
                            dtype=np.int64) == rows * vocab:
            gathers.append(line.strip()[:160])
        if re.search(rf"\[{rows},{vocab}\]\S*\)? sort\(", line):
            sorts.append(line.strip()[:160])
    return gathers, sorts


@pytest.mark.parametrize("program,rows,vocab", [
    ("sampler", 48, 50257),     # gpt2l_chat_*: 48 slots x GPT-2's vocabulary
    ("sampler", 8, 151936),     # st21b_longdoc_steady: 8 x SmallThinker's
    ("sampler", 40, 154880),    # glm47f_agent_saturated: 40 x GLM's
    ("sampler", 48, 65536),     # lfm2_extract_saturated: 48 x LFM2's
    ("_tick_paged", _SLOTS, 512),
])
def test_sampler_sorts_nothing_and_gathers_nothing_vocab_wide_on_v5e(
        v5e_chip, monkeypatch, program, rows, vocab):
    """``sample_logits_batched`` at the benchmark cells' shapes, and the
    GPT-2-large ``_tick_paged`` this module builds, compiled for the
    described chip: no sort over ``[rows, V]`` and no gather that yields
    ``rows x V`` elements. The parent of PR 31 held two sorts and three
    such gathers (``take_along_axis`` by the sort's permutation and by
    its inverse): 72 ms of a 105 ms tick in the saturated cell; PR 31
    left one sort, 9 ms of glm-4.7-flash's 25.6 ms tick, which PR 39's
    threshold search replaced."""
    shapes = functools.partial(_shapes_on, v5e_chip)
    if program == "sampler":
        from pddl_tpu.models.gpt import sample_logits_batched

        lowered = jax.jit(
            lambda key, logits, t, k, p: sample_logits_batched(
                key, logits, temperature=t, top_k=k, top_p=p)).lower(
            *shapes(jax.eval_shape(lambda: jax.random.key(0)),
                    jax.ShapeDtypeStruct((rows, vocab), jnp.bfloat16),
                    jax.ShapeDtypeStruct((rows,), jnp.float32),
                    jax.ShapeDtypeStruct((rows,), jnp.int32),
                    jax.ShapeDtypeStruct((rows,), jnp.float32)))
    else:
        eng = _paged_engine_for_v5e("gpt_20x64")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        lowered = eng._tick_p.lower(*shapes(*eng._tick_args()))
    gathers, sorts = _vocab_wide_gathers_and_sorts(
        lowered.compile().as_text(), rows, vocab)
    assert not gathers, "vocabulary-wide gathers\n" + "\n".join(gathers)
    assert not sorts, "sorts over [rows, V]\n" + "\n".join(sorts)


def test_smallthinker_cell_programs_compile_for_v5e(v5e_chip, monkeypatch):
    """The benchmark cell `st21b_longdoc_steady`'s own `_tick_paged` and
    `_chunk_paged` at the published widths (2560, 28 q / 4 kv heads of
    128, window 4096, 64 ReGLU experts of 768 top-6, vocabulary 151,936,
    16,384 positions; 8 slots, block 16, 8,193 blocks, 2,048-wide chunks),
    two layers (one full-attention NoPE, one window+RoPE), weights as
    shapes. The Mosaic kernel with `window` and 7 q heads a kv head is in
    the tick; the pool leaves `bf16[8193,4,16,256]` are aliased and never
    copied; no dispatch tensor anywhere near `[S, N, S]` exists; and no
    `prefill_len`-wide second program is built beside a 2,048-wide chunk."""
    from pddl_tpu.models.llama import SmallThinker_21B_A3B
    from pddl_tpu.serve import ServeEngine

    model = SmallThinker_21B_A3B(depth=2, dtype=jnp.bfloat16,
                                 param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32), train=False))[
            "params"]
    eng = ServeEngine(model, {"params": params}, paged=True, max_slots=8,
                      prefill_len=12288, prefix_block_size=16,
                      prefix_cache_blocks=8193, prefix_chunk=2048)
    assert not eng._has_wide
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    shapes = functools.partial(_shapes_on, v5e_chip)
    pool_shape = "bf16[8193,4,16,256]"
    weight_dims = {",".join(map(str, leaf.shape))
                   for leaf in jax.tree.leaves(params)}
    weight_dims |= {"2560,151936", "151936,2560"}   # either orientation
    programs = {
        "_tick_paged": (eng._tick_p, eng._tick_args()),
        "_chunk_paged": (eng._chunk_p, eng._chunk_args_paged(eng._chunk)),
    }
    for name, (prog, args) in programs.items():
        compiled = prog.lower(*shapes(*args)).compile()
        text = compiled.as_text()
        header = text.split("\n", 1)[0]
        assert f"jit_{name}" in header
        # One attention kernel call a layer in the tick (window and
        # NoPE-global), one expert kernel call a layer in both.
        kernels = _assert_expert_kernels(text, routed_layers=2)
        assert len(kernels["attention"]) == (
            2 if name == "_tick_paged" else 0)
        shape = r"\(?" + re.escape(pool_shape)
        moved = [line.strip()[:160] for line in text.splitlines()
                 if re.search(rf"= {shape}\S* (copy|copy-start|copy-done)\(",
                              line)]
        assert not moved, f"{name}: pool-sized copies\n" + "\n".join(moved)
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry",
                            header).group(1)
        entry = re.search(
            r"entry_computation_layout=\{\((.*)\)->\((.*)\)\}", header)
        params_in = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", entry.group(1))
        pool_params = {i for i, p in enumerate(params_in)
                       if p.startswith(pool_shape)}
        assert len(pool_params) == 2
        aliased = {int(m) for m in re.findall(r"\((\d+), \{\}, ", aliases)}
        assert pool_params <= aliased, (name, aliases)
        # The largest array any instruction makes (pool and weight
        # matrices apart): far under the [tokens, experts, tokens] of a
        # capacity-S dispatch, 268 M entries at this chunk.
        tokens = eng._chunk if name == "_chunk_paged" else 8
        largest = max(
            int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            for dims in re.findall(r"= \(?\w+\[([\d,]*)\]", text)
            if "8193" not in dims and dims not in weight_dims)
        assert largest < 64 * 2048 * 2048 // 8, (name, tokens, largest)


def test_glm_flash_cell_programs_compile_for_v5e(v5e_chip, monkeypatch):
    """The benchmark cell `glm47f_agent_saturated`'s own `_tick_paged` and
    `_chunk_paged` at the published widths (2048, 20 latent heads over a
    512 + 64-value entry, 64 SwiGLU experts of 1,536 top-4 beside a shared
    one, a dense first layer of 10,240, vocabulary 154,880; 40 slots of
    20,480 positions, block 16, 2,048-wide chunks), two layers (the dense
    one and a routed one), weights as shapes. The ONE paged Mosaic kernel
    is in the tick, a call a layer, on the leaf `bf16[51201,1,16,640]`;
    every leaf is aliased and never copied; no chunk-wide second program
    is built."""
    from pddl_tpu.models.llama import GLM_4_7_Flash
    from pddl_tpu.serve import ServeEngine

    model = GLM_4_7_Flash(depth=2, max_len=20480, dtype=jnp.bfloat16,
                          param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32), train=False))[
            "params"]
    eng = ServeEngine(model, {"params": params}, paged=True, max_slots=40,
                      prefill_len=16384, prefix_block_size=16,
                      prefix_cache_blocks=40 * 1280 + 1, prefix_chunk=2048)
    assert not eng._has_wide
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    shapes = functools.partial(_shapes_on, v5e_chip)
    pool_shape = "bf16[51201,1,16,640]"
    leaves = [leaf for leaf in jax.tree.leaves(eng._cache) if leaf.ndim == 4]
    assert [f"bf16[{','.join(map(str, x.shape))}]" for x in leaves] == \
        [pool_shape] * 2
    programs = {
        "_tick_paged": (eng._tick_p, eng._tick_args()),
        "_chunk_paged": (eng._chunk_p, eng._chunk_args_paged(eng._chunk)),
    }
    for name, (prog, args) in programs.items():
        compiled = prog.lower(*shapes(*args)).compile()
        text = compiled.as_text()
        header = text.split("\n", 1)[0]
        assert f"jit_{name}" in header
        kernels = _assert_expert_kernels(text, routed_layers=1)
        assert len(kernels["attention"]) == (
            2 if name == "_tick_paged" else 0)
        shape = r"\(?" + re.escape(pool_shape)
        moved = [line.strip()[:160] for line in text.splitlines()
                 if re.search(rf"= {shape}\S* (copy|copy-start|copy-done)\(",
                              line)]
        assert not moved, f"{name}: pool-sized copies\n" + "\n".join(moved)
        assert compiled.memory_analysis().temp_size_in_bytes \
            < leaves[0].size * 2, name
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry",
                            header).group(1)
        entry = re.search(
            r"entry_computation_layout=\{\((.*)\)->\((.*)\)\}", header)
        params_in = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", entry.group(1))
        pool_params = {i for i, p in enumerate(params_in)
                       if p.startswith(pool_shape)}
        assert len(pool_params) == 2
        assert all(params_in[i].startswith(pool_shape + "{3,2,1,0:T(8,128)")
                   for i in pool_params)
        aliased = {int(m) for m in re.findall(r"\((\d+), \{\}, ", aliases)}
        assert pool_params <= aliased, (name, aliases)


def test_lfm2_cell_programs_compile_for_v5e(v5e_chip, monkeypatch):
    """The benchmark cell `lfm2_extract_saturated`'s own `_tick_paged` and
    `_chunk_paged` at the cell's whole size: the published widths (2048,
    32 q / 8 kv heads of 64 with q/k norm, 3-tap short convolutions, 64
    SwiGLU experts of 1,536 top-4, a dense first layer of 11,776,
    vocabulary 65,536), all 9 layers of the cut, 48 slots of 18,432
    positions, block 16, 2,048-wide chunks; weights and the cache tree as
    shapes (the pool is 3.6 GB: nothing is allocated). The paged Mosaic
    kernel is in the tick, a call an ATTENTION layer, on the leaf
    `bf16[55297,8,16,128]` (K and V in exactly 128 lanes); a convolution
    layer has no pool and no kernel, its state `bf16[48,2,2048]` a slot;
    every pool and state leaf is aliased, no pool is copied; the
    arguments and temporaries are what `reckoned_bytes` states and fit
    the chip's 16.9 GB `bytes_limit`."""
    import json
    import pathlib

    import pddl_tpu.serve.engine as engine_module
    from pddl_tpu.models.llama import LFM2_24B_A2B
    from pddl_tpu.serve import ServeEngine

    cfg = json.loads((pathlib.Path(__file__).parent.parent / "chipbench"
                      / "configs" / "lfm2-24b-a2b.json").read_text())
    eng_cfg = cfg["engine"]
    model = LFM2_24B_A2B(depth=cfg["num_hidden_layers"],
                         max_len=eng_cfg["max_len"], dtype=jnp.bfloat16,
                         param_dtype=jnp.bfloat16)
    assert list(model.layer_types) == cfg["layer_types"]
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32), train=False))[
            "params"]
    build = engine_module.paged_decode_cache
    monkeypatch.setattr(engine_module, "paged_decode_cache",
                        lambda *a: jax.eval_shape(lambda: build(*a)))
    eng = ServeEngine(model, {"params": params}, paged=True,
                      max_slots=eng_cfg["max_slots"],
                      prefill_len=eng_cfg["prefill_len"],
                      prefix_block_size=eng_cfg["block_size"],
                      prefix_cache_blocks=eng_cfg["pool_blocks"],
                      prefix_chunk=eng_cfg["prefill_chunk"])
    assert not eng._has_wide
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    shapes = functools.partial(_shapes_on, v5e_chip)
    pool_shape, state_shape = "bf16[55297,8,16,128]", "bf16[48,2,2048]"
    by_key = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng._cache):
        by_key.setdefault(str(path[-1].key), []).append(
            f"bf16[{','.join(map(str, leaf.shape))}]")
    assert by_key["cached_kv"] == [pool_shape] * 2
    assert by_key["slot_state"] == [state_shape] * 7
    stated = lambda key: [int(x.replace(",", "")) for x in re.findall(
        r"\d{1,3}(?:,\d{3}){2,}", cfg["reckoned_bytes"][key])]
    programs = {
        "_tick_paged": (eng._tick_p, eng._tick_args(), 0),
        "_chunk_paged": (eng._chunk_p, eng._chunk_args_paged(eng._chunk), 1),
    }
    for name, (prog, args, which) in programs.items():
        compiled = prog.lower(*shapes(*args)).compile()
        text = compiled.as_text()
        header = text.split("\n", 1)[0]
        assert f"jit_{name}" in header
        kernels = _assert_expert_kernels(
            text, routed_layers=sum(model.moe_layout))   # 8 of 9
        assert len(kernels["attention"]) == (
            2 if name == "_tick_paged" else 0)
        shape = r"\(?" + re.escape(pool_shape)
        moved = [line.strip()[:160] for line in text.splitlines()
                 if re.search(rf"= {shape}\S* (copy|copy-start|copy-done)\(",
                              line)]
        assert not moved, f"{name}: pool-sized copies\n" + "\n".join(moved)
        memory = compiled.memory_analysis()
        args_b, temp_b = (memory.argument_size_in_bytes,
                          memory.temp_size_in_bytes)
        assert args_b == pytest.approx(stated("arguments")[which], rel=0.02)
        # `reckoned_bytes` is PR 38's compile (a benchmark file: only a
        # `benchmark` PR restates it). Since PR 39 the tick's sampler
        # holds one float32 [slots, vocabulary] row more, the
        # probabilities its threshold search reads pass after pass.
        sampler_row = (eng_cfg["max_slots"] * cfg["vocab_size"] * 4
                       if name == "_tick_paged" else 0)
        # With the expert kernel the chunk's peak no longer holds what
        # the loop's schedule kept live there: four routed layers'
        # combine gathers, [2,048 tokens, 4 choices, 2,048] bf16 each.
        combine_gathers = (4 * eng._chunk * 4 * 2048 * 2
                           if name == "_chunk_paged" else 0)
        assert temp_b == pytest.approx(
            stated("temporaries")[1 - which] + sampler_row
            - combine_gathers, rel=0.1)
        assert args_b + temp_b < 16.9e9, (name, args_b, temp_b)
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry",
                            header).group(1)
        entry = re.search(
            r"entry_computation_layout=\{\((.*)\)->\((.*)\)\}", header)
        params_in = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", entry.group(1))
        held = {i for i, p in enumerate(params_in)
                if p.startswith((pool_shape, state_shape))}
        assert len(held) == 9
        aliased = {int(m) for m in re.findall(r"\((\d+), \{\}, ", aliases)}
        assert held <= aliased, (name, aliases)


@pytest.mark.parametrize("family,routed", [("smallthinker", 4), ("gpt", 0)])
def test_engine_counts_routed_layers_by_expert_path(v5e_chip, monkeypatch,
                                                    family, routed):
    """`ServeMetrics.moe_kernel_layers` / `moe_loop_layers` count the
    routed layers a program traced, by the path their FFN took, under
    the set-up stage open: the engine's build here on the CPU takes the
    loop, the tick and chunk programs lowered for the described chip
    with the TPU selection forced take the kernel, one a routed layer;
    a model without experts counts nothing."""
    import pddl_tpu.serve.engine as engine_module
    from pddl_tpu.models.gpt import tiny_gpt
    from pddl_tpu.models.llama import tiny_smallthinker
    from pddl_tpu.serve import ServeEngine

    model = tiny_smallthinker() if family == "smallthinker" else tiny_gpt()
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32), train=False))[
            "params"]
    eng = ServeEngine(model, {"params": params}, max_slots=3,
                      prefill_len=64, prefix_block_size=4, prefix_chunk=16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shapes = functools.partial(_shapes_on, v5e_chip)
    for site, prog, args in (
            ("warmup:tick", eng._tick_p, eng._tick_args()),
            ("warmup:chunk_prefill", eng._chunk_p,
             eng._chunk_args_paged(eng._chunk))):
        with engine_module._setup_stage(site, eng.metrics):
            prog.lower(*shapes(*args))
    snap = eng.metrics.snapshot()
    assert {k: v for k, v in snap["moe_kernel_layers"].items() if v} == (
        {"warmup:tick": routed, "warmup:chunk_prefill": routed}
        if routed else {})
    assert {k: v for k, v in snap["moe_loop_layers"].items() if v} == (
        {"build": routed} if routed else {})


@pytest.mark.parametrize("heads,kv_heads", [(12, 12), (12, 4)])
def test_flash_forward_and_fused_backward_compile_for_v5e(v5e_chip, heads,
                                                          kv_heads):
    """B8 S2048 D64 causal, bf16: the GPT-small / Llama-small training
    shape, forward and the fused single-sweep backward together."""
    def sds(h):
        return jax.ShapeDtypeStruct((8, h, 2048, 64), jnp.bfloat16,
                                    sharding=v5e_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    _compile_with_kernel(jax.grad(loss, argnums=(0, 1, 2)),
                         sds(heads), sds(kv_heads), sds(kv_heads))


@pytest.mark.parametrize("seq,kernel", [
    (1000, True),   # largest divisor <= 512 is 500: not a sublane multiple
    (543, False),   # 3 x 181: no legal tiling, the reference serves it
])
def test_flash_lengths_off_the_block_grid_compile_for_v5e(v5e_chip, seq,
                                                          kernel):
    """A block the interpreter runs happily (500 or 181 rows) is refused
    by the TPU lowering unless it is a multiple of 8 or the whole
    dimension; the block choice must know that, and fall back to the
    reference only where no legal block exists."""
    shape = jax.ShapeDtypeStruct((1, 12, seq, 64), jnp.bfloat16,
                                 sharding=v5e_chip)
    _compile_with_kernel(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        shape, shape, shape, kernel=kernel)
