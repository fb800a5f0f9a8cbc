"""Which named scope every operation of a step program belongs to
(``obs/program_scopes.py``), and the route that tells
(``GET /debug/programs``): the pure function over a written-out HLO
text, then a tiny CPU engine of every family behind the app."""
import dataclasses
import json
import types

import pytest

from distributed_llm_tpu.config import tiny_batched_cluster
from distributed_llm_tpu.obs import Observability
from distributed_llm_tpu.obs.program_scopes import (innermost_scope,
                                                    op_scopes, scope_stack)

# -- the pure function ---------------------------------------------------------

HLO = '''HloModule jit_decode_tick, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0.1: f32[4], param_1.2: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %param_1.2 = f32[4]{0} parameter(1)
  %multiply.3 = f32[4]{0} multiply(%param_0.1, %param_1.2), metadata={op_name="jit(decode_tick)/while/body/closed_call/mixer_proj/kv_write/mul" stack_frame_id=3}
  ROOT %add.4 = f32[4]{0} add(%multiply.3, %param_1.2), metadata={op_name="jit(decode_tick)/while/body/closed_call/mixer_proj/attention/add"}
}

%fused_computation.1 (param_0.3: f32[4]) -> f32[4] {
  %param_0.3 = f32[4]{0} parameter(0)
  %exp.1 = f32[4]{0} exponential(%param_0.3), metadata={op_name="jit(decode_tick)/while/body/closed_call/mixer_proj/attention/bnk,bkc->bnc/exp"}
  ROOT %negate.1 = f32[4]{0} negate(%exp.1), metadata={op_name="jit(decode_tick)/while/body/closed_call/mixer_proj/neg"}
}

%fused_computation.2 (param_0.5: f32[4]) -> (f32[4], f32[]) {
  %param_0.5 = f32[4]{0} parameter(0)
  %constant.7 = f32[] constant(0), metadata={op_name="jit(decode_tick)/step_scan/while/body/closed_call/step_inputs/mul"}
  %convolution.1 = f32[4]{0} convolution(%param_0.5, %param_0.5), dim_labels=b_i->b, metadata={op_name="jit(decode_tick)/while/body/closed_call/mixer_proj/dot_general"}
  %multiply.8 = f32[4]{0} multiply(%convolution.1, %convolution.1), metadata={op_name="jit(decode_tick)/while/body/closed_call/ffn/mul"}
  %reduce.8 = f32[] reduce(%multiply.8, %constant.7), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(decode_tick)/while/body/closed_call/ffn/reduce_sum"}
  ROOT %tuple.8 = (f32[4]{0}, f32[]) tuple(%convolution.1, %reduce.8)
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(decode_tick)/while/body/closed_call/ffn/reduce_sum"}
}

%body.2 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.1 = f32[4]{0} get-tuple-element(%arg), index=1
  %copy.7 = f32[4]{0} copy(%get-tuple-element.1)
  %fusion.5 = f32[4]{0} fusion(%copy.7, %get-tuple-element.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode_tick)/while/body/closed_call/mixer_proj/attention/add"}
  %fusion.6 = f32[4]{0} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.1
  %paged_rows_decode.5 = f32[4]{0} custom-call(%fusion.6), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4]{0}}, backend_config={"custom_call_config": {"body": "calls=%not_a_computation, body=%x"}}, metadata={op_name="jit(decode_tick)/while/body/closed_call/mixer_proj/attention/jit(rows)/paged_rows_decode/pallas_call"}
  %fusion.7 = (f32[4]{0}, f32[]) fusion(%fusion.6), kind=kOutput, calls=%fused_computation.2
  %copy.8 = f32[4]{0} copy(%paged_rows_decode.5)
  %get-tuple-element.2 = s32[] get-tuple-element(%arg), index=0
  ROOT %tuple.3 = (s32[], f32[4]{0}) tuple(%get-tuple-element.2, %copy.8)
}

%cond.2 (arg.1: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.3 = s32[] get-tuple-element(%arg.1), index=0
  %constant.1 = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%get-tuple-element.3, %constant.1), direction=LT, metadata={op_name="jit(decode_tick)/layer_scan/while/cond/lt"}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %constant.2 = s32[] constant(0)
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%Arg_0.1)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %tuple.1 = (s32[], f32[4]{0}) tuple(%constant.2, %copy-done.1)
  %while.4 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond.2, body=%body.2, metadata={op_name="jit(decode_tick)/layer_scan/while"}
  %get-tuple-element.4 = f32[4]{0} get-tuple-element(%while.4), index=1
  %reduce.1 = f32[] reduce(%get-tuple-element.4, %constant.2), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(decode_tick)/ffn/reduce_sum"}
  ROOT %dot.2 = f32[4]{0} multiply(%get-tuple-element.4, %get-tuple-element.4), metadata={op_name="jit(decode_tick)/head/dot_general"}
}
'''


@pytest.mark.parametrize("op_name,stack", [
    ("jit(decode_tick)/while/body/closed_call/mixer_proj/attention/"
     "dot_general", ("mixer_proj", "attention")),
    ("jit(decode_tick)/jit(_threefry_split)/ContinuousBatchingEngine."
     "_decode_step.<locals>.decode_tick/while/body/closed_call/add", ()),
    ("jit(chunk_prefill)/cond/branch_1_fun/head/transpose(jvp(f))/mul",
     ("head",)),
    ("jit(f)/ffn/moe_experts/jit(grouped_ffn)/grouped_product_ffn/"
     "pallas_call", ("ffn", "moe_experts")),
    ("jit(f)/attention/bnk,bkc->bnc/dot_general", ("attention",)),
    ("jit(f)/mul", ()), ("", ()), (None, ()),
], ids=["nested", "closure name", "cond and transform", "pallas kernel",
        "einsum", "no scope", "empty", "none"])
def test_scope_stack_keeps_named_scopes_and_nothing_of_jaxs(op_name, stack):
    assert scope_stack(op_name) == stack
    assert innermost_scope(op_name) == (stack[-1] if stack else None)


@pytest.fixture(scope="module")
def hand_map():
    return op_scopes(HLO)


@pytest.mark.parametrize("name,scope,mixed", [
    ("dot.2", "head", False),                   # a top-level operation
    ("reduce.1", "ffn", False),
    ("paged_rows_decode.5", "attention", False),  # a custom call, in a body
    ("fusion.5", "attention", True),            # kv_write fused with attention
    ("fusion.6", "mixer_proj", False),          # its root's; the scopes nest
    ("fusion.7", "mixer_proj", False),          # the product's, not the sums'
    ("lt.1", "layer_scan", False),              # a loop's own condition
    ("copy.7", "attention", False),             # no op_name: what reads it
    ("copy-start.1", "layer_scan", False),      # ... through the loop's name
    ("copy-done.1", "layer_scan", False),
    ("copy.8", "attention", False),             # ... else what made it
], ids=lambda v: str(v))
def test_the_map_of_a_written_out_program(hand_map, name, scope, mixed):
    assert hand_map[name] == {"scope": scope, "mixed": mixed}


def test_the_map_lists_what_a_trace_can_show_and_nothing_else(hand_map):
    """No wrapper, nothing that runs nothing, nothing from inside a
    fused computation or a reducer; names without their ``%``."""
    assert sorted(hand_map) == sorted([
        "copy-start.1", "copy-done.1", "reduce.1", "dot.2", "copy.7",
        "fusion.5", "fusion.6", "fusion.7", "paged_rows_decode.5", "copy.8",
        "lt.1"])
    assert op_scopes("") == {} and op_scopes("HloModule empty\n") == {}


def test_an_instruction_nothing_names_stays_null():
    """Without metadata an instruction takes a neighbour's scope; where
    the loop it reads from has no name either, it has none."""
    text = HLO.replace(', metadata={op_name="jit(decode_tick)/head/'
                       'dot_general"}', "")
    assert op_scopes(text)["dot.2"] == {"scope": "layer_scan",
                                        "mixed": False}
    text = text.replace(', metadata={op_name="jit(decode_tick)/layer_scan/'
                        'while"}', "")
    got = op_scopes(text)
    assert got["dot.2"] == got["copy-start.1"] == {"scope": None,
                                                   "mixed": False}
    assert got["reduce.1"]["scope"] == "ffn"


# -- a program's pool-sized moves (ISSUE 57) -----------------------------------

# ``kimi-linear-48b-a3b``'s tick on PR 56's tree, the lines that matter
# (compile for a described v5e, PR 57): the latent pool rested block-minor
# and was copied into row-major before the step loop and back after it;
# the conv tails' pair of copies is under the floor.
POOL_HLO = '''HloModule jit_decode_tick

%fused_computation.35 (p.0: bf16[2,1281,64,576], p.1: bf16[16,576]) -> bf16[2,1281,64,576] {
  %p.0 = bf16[2,1281,64,576]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %p.1 = bf16[16,576]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %scatter.1 = bf16[2,1281,64,576]{3,2,1,0:T(8,128)(2,1)} scatter(%p.0, %p.1), to_apply=%add
}

ENTRY %main.1 (pool__c__.1: bf16[2,1281,64,576], pool__t__.1: bf16[7,16,3,12288], rows: bf16[16,576]) -> (bf16[2,1281,64,576], bf16[7,16,3,12288]) {
  %pool__c__.1 = bf16[2,1281,64,576]{1,3,2,0:T(8,128)(2,1)} parameter(0)
  %pool__t__.1 = bf16[7,16,3,12288]{3,1,2,0:T(8,128)(2,1)} parameter(1)
  %rows = bf16[16,576]{1,0:T(8,128)(2,1)} parameter(2)
  %copy.150 = bf16[2,1281,64,576]{3,2,1,0:T(8,128)(2,1)} copy(%pool__c__.1)
  %copy.151 = bf16[7,16,3,12288]{3,2,1,0:T(4,128)(2,1)} copy(%pool__t__.1)
  %fusion.1183 = bf16[2,1281,64,576]{3,2,1,0:T(8,128)(2,1)} fusion(%copy.150, %rows), kind=kCustom, calls=%fused_computation.35
  %copy.161 = bf16[2,1281,64,576]{1,3,2,0:T(8,128)(2,1)} copy(%fusion.1183)
  %copy.162 = bf16[7,16,3,12288]{3,1,2,0:T(8,128)(2,1)} copy(%copy.151)
  ROOT %tuple.1 = (bf16[2,1281,64,576]{1,3,2,0:T(8,128)(2,1)}, bf16[7,16,3,12288]{3,1,2,0:T(8,128)(2,1)}) tuple(%copy.161, %copy.162)
}
'''


def _pool_shapes(**arrays):
    import jax
    import jax.numpy as jnp
    return {key: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
            for key, shape in arrays.items()}


def test_pool_sized_moves_counts_copies_and_leaves_in_place_writes():
    """A ``copy`` of a pool-sized array counts, the fusion whose root
    writes it in place does not, one whose root makes a new array does;
    an array under ``POOL_SIZED_BYTES`` (the conv tails) is not looked
    for, and a row-major pool's program reads ``{}``."""
    from distributed_llm_tpu.obs.program_scopes import (
        POOL_SIZED_BYTES, pool_sized_moves, row_major)
    pool = _pool_shapes(c=(2, 1281, 64, 576), t=(7, 16, 3, 12288))
    assert pool["t"].size * 2 < POOL_SIZED_BYTES < pool["c"].size * 2
    assert pool_sized_moves(POOL_HLO, pool) == {"copy": 2}
    moved = POOL_HLO.replace("%scatter.1 =", "%add.9 =").replace(
        "scatter(%p.0, %p.1), to_apply=%add", "add(%p.0, %p.0)")
    assert pool_sized_moves(moved, pool) == {"copy": 2, "fusion": 1,
                                             "add": 1}
    in_place = "\n".join(
        line.replace("%copy.150", "%pool__c__.1").replace(
            "%copy.161", "%fusion.1183")
        for line in POOL_HLO.splitlines()
        if " copy(%pool__c__" not in line and " copy(%fusion" not in line)
    assert pool_sized_moves(in_place, pool) == {}
    # An int32 vector of owners beside the arrays is no pool-sized array.
    import jax
    import jax.numpy as jnp
    pool["owner"] = jax.ShapeDtypeStruct((16,), jnp.int32)
    assert pool_sized_moves(POOL_HLO, pool) == {"copy": 2}
    assert row_major((0, 1, 2, 3)) and not row_major((0, 2, 3, 1))


# -- the route, over a tiny engine of every family -----------------------------

# Per preset: the scopes PERF.md section 3 lists for its family, by the
# program that should hold them (both, the tick alone, the chunk alone).
FAMILIES = {
    "nano_test": (
        {"kv_write", "attention", "ffn", "mixer_proj"}, set(), set()),
    "latent_test": (
        {"latent_attention", "kv_write", "moe_router", "moe_experts",
         "shared_expert", "hyper_connection", "ffn", "mixer_proj"},
        set(), set()),
    "hybrid_test": (
        {"ssm_in_proj", "ssm_conv", "ssm_gate_norm", "attention",
         "kv_write", "moe_router", "moe_experts", "shared_expert",
         "mixer_proj"}, {"ssm_step"}, {"ssm_scan"}),
    "hybrid_mamba1_test": (
        {"ssm_in_proj", "ssm_conv", "ssm_inner_norms", "kv_write",
         "attention", "ffn", "mixer_proj"}, {"ssm_step"}, {"ssm_scan"}),
    "hybrid_cca_test": (
        {"cca_proj", "cca_conv", "cca_qk_norm", "kv_write", "attention",
         "moe_router", "moe_experts"}, set(), set()),
    "hybrid_kda_test": (
        {"kda_proj", "kda_conv", "kda_gate", "kda_out_norm",
         "latent_attention", "kv_write", "moe_router", "moe_experts",
         "shared_expert", "ffn"}, {"kda_step"}, {"kda_scan"}),
    "shared_kv_test": (
        {"ssm_in_proj", "ssm_conv", "window_attention", "shared_kv_write",
         "shared_kv_attention", "gated_memory", "diff_combine", "ffn",
         "mixer_proj"}, {"ssm_step"}, {"ssm_scan"}),
}
# ``embed`` is one gather, which the CPU's compiler fuses into the first
# norm of one family's tick: asked for in some program of every preset.
EVERYWHERE = {"head", "sample", "layer_scan"}


@pytest.fixture(scope="module", params=list(FAMILIES))
def asked(request):
    """A tiny cluster whose nano tier serves the preset, warmed, and what
    five requests of ``GET /debug/programs`` found: the engine's cache
    before the first, after a listing and after the ticks alone were
    asked about, the whole document, and a second one with the engine's
    lowering taken away."""
    from distributed_llm_tpu.serving.app import create_app
    from distributed_llm_tpu.serving.router import Router
    base = tiny_batched_cluster()
    cluster = dataclasses.replace(base, nano=dataclasses.replace(
        base.nano, model_preset=request.param, decode_batch=4,
        kv_block_size=16, prefill_buckets=(16, 32, 64, 128),
        prefill_chunk_tokens=16, enable_prefix_cache=False))
    router = Router(cluster=cluster,
                    observability=Observability(slow_ms=None))
    try:
        client = create_app(router=router).test_client()
        engine = router.tiers["nano"].server_manager.engine()
        compiled = {stage: sorted(engine._compiled.get(stage, ()))
                    for stage in ("decode", "chunk_prefill")}
        before = dict(engine._program_maps)
        listing = client.get("/debug/programs?ops=0").get_json()
        listed = dict(engine._program_maps)
        ticks = client.get("/debug/programs?stage=decode").get_json()
        after_ticks = sorted(engine._program_maps)
        first = client.get("/debug/programs")
        built = dict(engine._program_maps)

        def refuse(*a, **kw):
            raise AssertionError("a second request lowered a program")
        engine.lower_pool_program = refuse
        second = client.get("/debug/programs")
        yield types.SimpleNamespace(
            preset=request.param, client=client, compiled=compiled,
            pool={key: x.shape for key, x in engine.pool.items()},
            stats=client.get("/stats").get_json(),
            before=before, listing=listing, listed=listed, ticks=ticks,
            after_ticks=after_ticks, built=built, status=first.status_code,
            doc=first.get_json(), text=first.get_data(as_text=True)
            if hasattr(first, "get_data") else first.body.decode(),
            again=second.get_json())
    finally:
        router.drain()


def test_the_map_is_not_built_until_asked_and_only_once(asked):
    assert asked.before == {}
    n = sum(len(v) for v in asked.compiled.values())
    assert n >= 2 and len(asked.built) == n
    assert set(asked.built) == {(stage, key) for stage, keys in
                                asked.compiled.items() for key in keys}
    assert asked.again == asked.doc          # and lowered nothing to say it


def test_a_listing_builds_nothing_and_a_selection_only_what_it_names(asked):
    assert asked.listed == {}
    listed = asked.listing["tiers"]["nano"]
    assert [{k: v for k, v in e.items()
             if k not in ("ops", "built_s", "pool_sized_moves")}
            for e in asked.doc["tiers"]["nano"]] == listed
    assert asked.after_ticks == [("decode", key)
                                 for key in asked.compiled["decode"]]
    ticks = asked.ticks["tiers"]["nano"]
    assert ticks == [e for e in asked.doc["tiers"]["nano"]
                     if e["stage"] == "decode"]
    window = ticks[0]["window_tokens"]
    one = asked.client.get(f"/debug/programs?stage=decode&window_tokens="
                           f"{window},7").get_json()["tiers"]["nano"]
    assert [e["window_tokens"] for e in one] == [window]
    for bad in ("stage=prefill", "window_tokens=wide"):
        assert asked.client.get(f"/debug/programs?{bad}").status_code == 400


def test_the_route_returns_an_entry_for_each_warmed_program(asked):
    assert asked.status == 200
    assert json.loads(asked.text) == asked.doc          # the document is JSON
    entries = asked.doc["tiers"]["nano"]
    ticks = [e for e in entries if e["stage"] == "decode"]
    chunks = [e for e in entries if e["stage"] == "chunk_prefill"]
    assert [e["window_tokens"] // 16 for e in ticks] == [
        key[0] for key in asked.compiled["decode"]]
    assert [(e["chunk_tokens"], e["window_tokens"]) for e in chunks] == [
        tuple(key) for key in asked.compiled["chunk_prefill"]]
    for e in entries:
        tick = e["stage"] == "decode"
        assert e["program"] == ("jit_decode_tick" if tick
                                else "jit_chunk_prefill")
        # A tick's form a rung; a chunk program's where it attends latent
        # rows (the tiny presets' widths keep the plain form: ISSUE 61).
        assert e["attention_form"] is not None if tick else (
            e["attention_form"] in (None, "plain"))
        assert (e["chunk_tokens"] is None) == tick
        assert set(e["built_s"]) == {"lower", "compile", "read"}
        assert len(e["ops"]) > 50
        assert all(set(v) == {"scope", "mixed"} and
                   isinstance(v["mixed"], bool) for v in e["ops"].values())
        assert not any(name.startswith(("while", "conditional", "call",
                                        "%")) or "@" in name
                       for name in e["ops"])


def test_the_route_counts_pool_moves_and_stats_says_how_the_pool_rests(
        asked):
    """ISSUE 57: every built entry carries ``pool_sized_moves`` (a tiny
    pool holds no pool-sized array, so the count is empty here; the real
    sizes are tests/test_tpu_compile.py's) and GET /stats
    ``tiers.<tier>.pool.formats`` every pool array's format at rest: on
    the CPU row-major, the shape the engine's pool has."""
    for e in asked.doc["tiers"]["nano"]:
        assert e["pool_sized_moves"] == {}
    for e in asked.listing["tiers"]["nano"]:
        assert "pool_sized_moves" not in e           # nothing was built
    formats = asked.stats["tiers"]["nano"]["pool"]["formats"]
    assert {key: tuple(f["shape"]) for key, f in formats.items()} \
        == asked.pool
    for key, f in formats.items():
        assert f["row_major"] is True, (key, f)
        assert f["major_to_minor"] == list(range(len(f["shape"])))
        assert set(f) == {"shape", "dtype", "major_to_minor", "tiling",
                          "row_major"}


def test_every_scope_of_the_family_is_in_the_program_that_holds_it(asked):
    both, tick_only, chunk_only = FAMILIES[asked.preset]
    for e in asked.doc["tiers"]["nano"]:
        scopes = {v["scope"] for v in e["ops"].values()}
        tick = e["stage"] == "decode"
        want = both | EVERYWHERE | (tick_only if tick else chunk_only)
        assert want <= scopes, (e["stage"], sorted(want - scopes))
        assert not (chunk_only if tick else tick_only) & scopes
        assert ("step_scan" in scopes) == tick
    assert any(v["scope"] == "embed" for e in asked.doc["tiers"]["nano"]
               for v in e["ops"].values())


def test_head_is_in_every_tick_and_little_is_left_unscoped(asked):
    for e in asked.doc["tiers"]["nano"]:
        ops = e["ops"]
        assert any(v["scope"] == "head" for v in ops.values())
        unscoped = [k for k, v in ops.items() if v["scope"] is None]
        assert len(unscoped) < 0.15 * len(ops), unscoped
