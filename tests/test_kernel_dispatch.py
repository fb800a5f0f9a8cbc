"""Measured per-(kind, length) kernel dispatch.

ops/attention.py's dispatching wrappers consult bench/ab_dispatch.json —
written by `ab_kernels micro --write-dispatch` on real hardware — instead
of the round-1 blanket DLLM_ATTENTION=xla pin.  These tests pin the
override precedence and exercise the micro harness end-to-end on CPU.
"""

import json

import pytest

from distributed_llm_tpu.ops import attention as A


@pytest.fixture
def table(monkeypatch):
    def set_table(t):
        monkeypatch.setattr(A, "_DISPATCH_TABLE", t)
    monkeypatch.delenv("DLLM_ATTENTION", raising=False)
    return set_table


def test_measured_table_demotes_per_length(table):
    table({"decode": {"default": "xla", "256": "pallas", "2048": "xla"}})
    # Exact rung wins.
    assert A._choose("pallas", "decode", 256) == "pallas"
    assert A._choose("pallas", "decode", 2048) == "xla"
    # Off-ladder shapes snap to the NEAREST measured rung (the
    # batched engine's trimmed paged windows take many values; nearest
    # rung beats the kind-wide default when rungs exist).
    assert A._choose("pallas", "decode", 320) == "pallas"
    assert A._choose("pallas", "decode", 1600) == "xla"
    # No numeric rungs at all: the kind-wide default applies.
    table({"decode": {"default": "xla"}})
    assert A._choose("pallas", "decode", 512) == "xla"
    # Unknown kind: engine's choice stands.
    table({"decode": {"default": "xla", "256": "pallas"}})
    assert A._choose("pallas", "paged_decode", 512) == "pallas"


def test_env_override_beats_measured_table(table, monkeypatch):
    table({"decode": {"default": "xla"}})
    monkeypatch.setenv("DLLM_ATTENTION", "pallas")
    assert A._choose("pallas", "decode", 512) == "pallas"
    monkeypatch.setenv("DLLM_ATTENTION", "xla")
    assert A._choose("pallas", "prefill", 512) == "xla"


def test_auto_stays_xla_table_not_consulted(table):
    # 'auto' (sharded/portable engines) never takes the Pallas family even
    # if the table would prefer it — a pallas_call has no GSPMD rule.
    table({"decode": {"default": "pallas"}})
    assert A._choose("auto", "decode", 512) == "xla"


def test_string_entry_and_missing_file(table):
    table({"prefill": "xla"})
    assert A._choose("pallas", "prefill", 1024) == "xla"
    table({})                        # no table: engine's choice stands
    assert A._choose("pallas", "prefill", 1024) == "pallas"


def test_registry_matches_consulted_kinds_and_ab_grid():
    """DISPATCH_KINDS is the contract surface: it must equal BOTH the
    set of kinds the dispatching wrappers actually consult (_choose /
    decode_kv_span call sites, scanned from source) AND the A/B
    harness's measurable case classes — a kernel kind cannot exist that
    the table schema or the measurement grid doesn't know about."""
    import inspect
    import re

    from distributed_llm_tpu.bench import ab_kernels

    src = inspect.getsource(A)
    consulted = set(re.findall(r'_choose\(\s*impl\s*,\s*"(\w+)"', src))
    assert consulted == set(A.DISPATCH_KINDS), (
        "ops/attention.py consults kinds the registry doesn't declare "
        f"(or vice versa): {consulted ^ set(A.DISPATCH_KINDS)}")
    assert set(ab_kernels.ALL_KINDS) == set(A.DISPATCH_KINDS)


def test_committed_table_covers_every_registered_kernel():
    """The shipped ab_dispatch.json must carry an entry (with a default)
    for EVERY registered dispatch kind — the table once fell silently
    behind the shipped kernels (paged_chunk
    had no row; chunk's pallas verdict predated the gen-2 rewrite)."""
    with open(A._DISPATCH_PATH) as f:
        data = json.load(f)
    table = data["dispatch"]
    missing = set(A.DISPATCH_KINDS) - set(table)
    assert not missing, f"dispatch table missing kinds: {sorted(missing)}"
    for kind, per_len in table.items():
        assert "default" in per_len, f"{kind} has no default entry"
        assert all(v in ("xla", "pallas")
                   for k, v in per_len.items() if k != "timeout_demoted")
    # Conservative-refresh invariant: a table whose kernel_gen is behind
    # the current kernels may keep pallas verdicts ONLY for kernel
    # families that generation did not rewrite (gen 2 rewrote the
    # decode/chunk families; prefill is unchanged since gen 1).
    from distributed_llm_tpu.ops.pallas_attention import KERNEL_GEN
    if data.get("kernel_gen") != KERNEL_GEN:
        for kind, per_len in table.items():
            if kind == "prefill":
                continue
            stale_pallas = {k: v for k, v in per_len.items()
                            if v == "pallas"}
            assert not stale_pallas, (
                f"{kind}: stale-gen pallas verdicts steer a rewritten "
                f"kernel: {stale_pallas}")


def test_micro_ab_writes_dispatch(tmp_path, monkeypatch):
    from distributed_llm_tpu.bench import ab_kernels
    out = tmp_path / "ab_dispatch.json"
    monkeypatch.setattr(ab_kernels, "DISPATCH_PATH", str(out))
    res = ab_kernels.micro_ab("nano", repeat=1, write_dispatch=True)
    assert res["cases"], "no kernel cases measured"
    kinds = {c["kind"] for c in res["cases"]}
    assert {"prefill", "decode", "chunk", "chunk_q8",
            "paged_decode"} <= kinds
    data = json.loads(out.read_text())
    assert set(data["dispatch"]) == kinds
    for per_len in data["dispatch"].values():
        assert all(v in ("xla", "pallas") for v in per_len.values())


def test_micro_ab_fast_mode_covers_all_kinds(tmp_path, monkeypatch):
    """The fast A/B (``ab_kernels micro --fast``) must still
    produce a table covering every dispatch kind, with per-kind defaults,
    and beat its liveness callback per case."""
    from distributed_llm_tpu.bench import ab_kernels
    out = tmp_path / "ab_dispatch.json"
    monkeypatch.setattr(ab_kernels, "DISPATCH_PATH", str(out))
    beats = []
    res = ab_kernels.micro_ab("nano", repeat=1, write_dispatch=True,
                              fast=True, beat=lambda: beats.append(1))
    kinds = {c["kind"] for c in res["cases"]}
    assert set(ab_kernels.ALL_KINDS) == kinds
    assert len(beats) == len(res["cases"]) and beats
    data = json.loads(out.read_text())
    for per_len in data["dispatch"].values():
        assert "default" in per_len


def test_micro_ab_kinds_subset_merges_into_prior_table(tmp_path,
                                                       monkeypatch):
    """A --kinds re-run (isolating a case class after a chip wedge) must
    MERGE into a same-backend table, not erase the other kinds' measured
    winners (code-review r3), and must reject unknown kind names."""
    import pytest

    from distributed_llm_tpu.bench import ab_kernels
    out = tmp_path / "ab_dispatch.json"
    monkeypatch.setattr(ab_kernels, "DISPATCH_PATH", str(out))
    ab_kernels.micro_ab("nano", repeat=1, write_dispatch=True, fast=True)
    before = json.loads(out.read_text())["dispatch"]
    assert "prefill" in before and "decode_q8" in before

    res = ab_kernels.micro_ab("nano", repeat=1, write_dispatch=True,
                              fast=True, kinds={"decode"})
    assert {c["kind"] for c in res["cases"]} == {"decode"}
    after = json.loads(out.read_text())["dispatch"]
    assert after["prefill"] == before["prefill"]        # preserved
    assert after["decode_q8"] == before["decode_q8"]    # preserved
    assert "decode" in after                            # re-measured

    with pytest.raises(ValueError, match="unknown kinds"):
        ab_kernels.micro_ab("nano", repeat=1, kinds={"deocde_q8"})


def test_dispatch_write_policy_hardware_beats_cpu(tmp_path):
    """bench/tune.py's backend policy, mirrored: a cpu fallback never
    clobbers a hardware table, but a hardware run may replace a stale
    cpu table — and starts CLEAN (no cross-backend winner mixing),
    while a same-backend partial run merges."""
    from distributed_llm_tpu.bench.ab_kernels import publish_dispatch
    out = str(tmp_path / "ab_dispatch.json")
    tpu_table = {"decode": {"256": "xla", "default": "xla"}}

    assert publish_dispatch("tpu", "m", tpu_table, path=out)
    # cpu fallback refused against a hardware table.
    assert not publish_dispatch("cpu", "m", {"prefill": {"default": "xla"}},
                                path=out)
    data = json.loads(open(out).read())
    assert data["backend"] == "tpu" and "prefill" not in data["dispatch"]

    # Same-backend partial run merges, keeping unmeasured kinds.
    assert publish_dispatch("tpu", "m",
                            {"prefill": {"default": "pallas"}}, path=out)
    data = json.loads(open(out).read())
    assert data["dispatch"]["decode"] == tpu_table["decode"]
    assert data["dispatch"]["prefill"] == {"default": "pallas"}

    # Hardware refresh over a stale cpu table starts clean.
    with open(out, "w") as f:
        json.dump({"backend": "cpu", "model": "m",
                   "dispatch": {"chunk": {"default": "xla"}}}, f)
    assert publish_dispatch("tpu", "m", tpu_table, path=out)
    data = json.loads(open(out).read())
    assert data["backend"] == "tpu"
    assert "chunk" not in data["dispatch"], "cross-backend winners mixed"


def test_micro_ab_numerics_gate_demotes_mismatch(tmp_path, monkeypatch):
    """A pallas leg whose outputs diverge from XLA on the measured
    backend must lose the dispatch slot even if it times faster — the
    interpreter-mode parity suite can't see a real-Mosaic miscompile."""
    from distributed_llm_tpu.bench import ab_kernels
    from distributed_llm_tpu.ops import pallas_attention as PA
    out = tmp_path / "ab_dispatch.json"
    monkeypatch.setattr(ab_kernels, "DISPATCH_PATH", str(out))

    orig = PA.flash_decode_attention

    def corrupted(q, k, v, pos):
        return orig(q, k, v, pos) * 3.0

    monkeypatch.setattr(PA, "flash_decode_attention", corrupted)
    res = ab_kernels.micro_ab("nano", repeat=1, write_dispatch=True,
                              fast=True, kinds={"decode"})
    assert all(c.get("numerics_mismatch") for c in res["cases"]), res["cases"]
    table = json.loads(out.read_text())["dispatch"]["decode"]
    assert set(table.values()) == {"xla"}, table


def test_micro_ab_records_rel_err(tmp_path, monkeypatch):
    from distributed_llm_tpu.bench import ab_kernels
    out = tmp_path / "ab_dispatch.json"
    monkeypatch.setattr(ab_kernels, "DISPATCH_PATH", str(out))
    res = ab_kernels.micro_ab("nano", repeat=1, fast=True,
                              kinds={"prefill"})
    for c in res["cases"]:
        assert c.get("rel_err") is not None and c["rel_err"] <= 0.05, c


def test_loader_provenance_flags_stale_kernel_gen(tmp_path, monkeypatch,
                                                  caplog):
    """A same-backend table whose kernel_gen is absent or behind the
    current Pallas kernels still dispatches, but the loader logs the
    staleness and dispatch_provenance() (surfaced at /stats) reports it —
    stale hardware conclusions must be visibly provisional."""
    import logging

    from distributed_llm_tpu.ops import pallas_attention as PA

    def load_with(payload):
        path = tmp_path / "tbl.json"
        path.write_text(json.dumps(payload))
        monkeypatch.setattr(A, "_DISPATCH_PATH", str(path))
        monkeypatch.setattr(A, "_DISPATCH_TABLE", None)
        monkeypatch.setattr(A, "_DISPATCH_META", None)
        with caplog.at_level(logging.WARNING,
                             logger="distributed_llm_tpu.ops.attention"):
            caplog.clear()
            return A.dispatch_provenance()

    # Pre-gen-stamp table (the committed r3 artifact's shape): stale.
    prov = load_with({"backend": "cpu", "model": "m",
                      "dispatch": {"decode": {"default": "xla"}}})
    assert prov["active"] and prov["stale_kernel_gen"]
    assert prov["kernel_gen"] is None
    assert prov["current_kernel_gen"] == PA.KERNEL_GEN
    assert any("provisional" in r.message for r in caplog.records)
    # The stale table still steers dispatch (re-measuring needs hardware).
    monkeypatch.delenv("DLLM_ATTENTION", raising=False)
    assert A._choose("pallas", "decode", 256) == "xla"

    # Current-gen table: clean, no warning.
    prov = load_with({"backend": "cpu", "kernel_gen": PA.KERNEL_GEN,
                      "dispatch": {"decode": {"default": "xla"}}})
    assert prov["active"] and not prov["stale_kernel_gen"]
    assert not caplog.records

    # Cross-backend table: inactive, gen not judged.
    prov = load_with({"backend": "tpu", "kernel_gen": 1,
                      "dispatch": {"decode": {"default": "xla"}}})
    assert not prov["active"] and not prov["stale_kernel_gen"]
    assert not caplog.records


def test_stale_kernel_gen_starts_clean(tmp_path):
    """A table measured against an older kernel generation must not mix
    with fresh measurements (publish starts clean on gen mismatch)."""
    from distributed_llm_tpu.bench.ab_kernels import publish_dispatch
    out = str(tmp_path / "ab_dispatch.json")
    assert publish_dispatch("tpu", "m",
                            {"decode": {"default": "xla"}}, path=out,
                            kernel_gen=1)
    assert publish_dispatch("tpu", "m",
                            {"prefill": {"default": "pallas"}}, path=out,
                            kernel_gen=2)
    data = json.loads(open(out).read())
    assert data["kernel_gen"] == 2
    assert "decode" not in data["dispatch"], "stale-gen winners mixed"
    # Same gen merges as usual.
    assert publish_dispatch("tpu", "m",
                            {"chunk": {"default": "pallas"}}, path=out,
                            kernel_gen=2)
    data = json.loads(open(out).read())
    assert set(data["dispatch"]) == {"prefill", "chunk"}


# -- the form follows the representation (ISSUE 30) ---------------------------

# bfloat16: what chip_smoke.KERNEL_TOL holds the Pallas kernels to against
# their XLA references (2e-2: the two round at different points).
_FORM_TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _token_major_case(n_q, n_kv, d, dtype, int8, *, batch=4, bs=16, mb=8,
                      layers=3, seed=0):
    """A WHOLE token-major pool ``[L, NB, bs, Nkv * D]`` (int8 with its
    scales ``[L, NB, bs, Nkv]``) and a batch whose slot 0 is idle (``pos``
    0, a table of trash blocks), slot 1 attends the last column of the
    window, the others somewhere inside."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_tpu.engine.paged_kv import TRASH_BLOCK
    from distributed_llm_tpu.ops.quant import quantize_kv_rows
    nb = batch * mb + 1
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (batch, n_q, d), jnp.float32).astype(dtype)
    pools = [jax.random.normal(k, (layers, nb, bs, n_kv, d), jnp.float32)
             for k in keys[1:]]
    scales = (None, None)
    if int8:
        (k, ks), (v, vs) = (quantize_kv_rows(p) for p in pools)
        pools, scales = [k, v], (ks, vs)
    else:
        pools = [p.astype(dtype) for p in pools]
    pools = [p.reshape(layers, nb, bs, n_kv * d) for p in pools]
    tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(batch, mb)
    tables = tables.at[0].set(TRASH_BLOCK)
    pos = jnp.array([0, mb * bs - 1, 17, 40][:batch], jnp.int32)
    return q, pools, scales, tables, pos


# What the served tick's two forms are asked for: ``merged`` the XLA path
# every engine off the chip or on a mesh takes (impl 'xla'); ``streamed``
# an engine that opted into kernels, where the measured table keeps the
# head-major kernels off the tick (as the committed table does): the
# kernel of ops/rows_attention.py, interpreted here.
_SERVED_FORMS = {
    "merged": ("xla", {}),
    "streamed": ("pallas", {"paged_decode": "xla", "ragged_decode": "xla",
                            "paged_decode_q8": "xla",
                            "ragged_decode_q8": "xla"}),
}


@pytest.mark.parametrize("form", list(_SERVED_FORMS))
@pytest.mark.parametrize("pool", ["model-dtype-pool", "int8-pool"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_q,n_kv,d", [(32, 32, 64), (32, 8, 64),
                                        (32, 8, 128)],
                         ids=["mha-d64", "gqa-d64", "gqa-d128"])
def test_served_forms_agree_with_decode_attention(table, n_q, n_kv, d, dtype,
                                                  pool, form):
    """The served tick's attention (``layer=i``: the whole token-major
    pool) in both its forms — the XLA gather attended merged, and the
    block table walked by the kernel with nothing gathered (ISSUE 45) —
    against ``decode_attention`` over the SAME rows with the head axis
    split off, the reference the Pallas kernels are held to as well: MHA
    and GQA at head 64, GQA at head 128, an int8 pool with its scales
    (GQA and int8 the kernel does not serve: the rule sends them to the
    XLA form whatever the engine opted into), an idle slot over the trash
    block, ``pos`` at 0 and at the window's last position."""
    import jax.numpy as jnp
    import numpy as np
    impl, measured = _SERVED_FORMS[form]
    table(measured)
    int8 = pool == "int8-pool"
    q, (kp, vp), (ks, vs), tables, pos = _token_major_case(
        n_q, n_kv, d, jnp.dtype(dtype), int8)
    assert A.decode_form(
        impl, "paged_decode" + ("_q8" if int8 else ""), n_q, d,
        tables.shape[1], kp.shape[2], kp.shape[3], kp.dtype
    ) == (form if n_kv == n_q and not int8 else "merged")
    layer = jnp.int32(1)
    got = A.paged_decode(q, kp, vp, tables, pos, impl=impl, k_scale=ks,
                         v_scale=vs, layer=layer)
    k_seq, v_seq = A._gather_pool_seq(q, kp, vp, tables, ks, vs, layer)
    assert k_seq.shape == (*tables.shape[:1], tables.shape[1] * kp.shape[2],
                           n_kv, d)
    want = A.decode_attention(q, k_seq, v_seq, pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = _FORM_TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    # The idle slot attends one trash row: its value, whatever the rest.
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32),
        np.asarray(jnp.repeat(v_seq[0, 0], n_q // n_kv, axis=0), np.float32),
        atol=tol, rtol=tol)
    # One code path for both tick shapes: the fused ragged tick's
    # fallback is byte-identical.
    ragged = A.ragged_decode(q, kp, vp, tables, pos, impl=impl, k_scale=ks,
                             v_scale=vs, layer=layer)
    np.testing.assert_array_equal(np.asarray(ragged), np.asarray(got))


def test_streamed_form_is_chosen_from_static_shapes(table, monkeypatch):
    """``decode_form``: the kernel takes the tick where the engine opted
    into kernels, every query head has a K/V head of its own and the rows
    fill whole lanes; the measured table still
    decides the head-major kernels, ``DLLM_ATTENTION=xla`` still switches
    every kernel off, and nothing else does."""
    import jax.numpy as jnp
    bf16 = jnp.bfloat16
    table({"paged_decode": "xla", "paged_decode_q8": "xla"})
    args = ("paged_decode", 32, 64, 4, 64)
    assert A.decode_form("pallas", *args, 2048, bf16) == "streamed"
    assert A.decode_form("auto", *args, 2048, bf16) == "merged"
    assert A.decode_form("pallas", *args, 2048, jnp.int8) == "merged"
    assert A.decode_form("pallas", "paged_decode_q8", 32, 64, 4, 64, 2048,
                         bf16) == "merged"
    # Query heads that share a K/V head (GQA 32/8: rows of 512 columns).
    assert A.decode_form("pallas", *args, 512, bf16) == "merged"
    # A row off the lanes (a tiny preset's), a block off the sublanes.
    assert A.decode_form("pallas", "paged_decode", 3, 32, 4, 64, 96,
                         bf16) == "merged"
    assert A.decode_form("pallas", "paged_decode", 32, 64, 4, 8, 2048,
                         bf16) == "merged"
    table({"paged_decode": "pallas"})
    assert A.decode_form("pallas", *args, 2048, bf16) == "split"
    table({"paged_decode": "xla"})
    monkeypatch.setenv("DLLM_ATTENTION", "xla")
    assert A.decode_form("pallas", *args, 2048, bf16) == "merged"


def test_head_major_views_keep_the_split_form(table):
    """Without a ``layer`` the pools are a hook's or a kernel test's
    per-layer head-major views: gathered ``[B, S, Nkv, D]`` and attended
    by ``decode_attention`` itself, bit for bit."""
    import jax.numpy as jnp
    import numpy as np
    table({})
    q, (kp, vp), _, tables, pos = _token_major_case(
        8, 4, 16, jnp.dtype("float32"), False)
    k_v, v_v, _, _ = A._layer_views(jnp.int32(2), 16, kp, vp)
    got = A.paged_decode(q, k_v, v_v, tables, pos, impl="xla")
    want = A.decode_attention(
        q, *A._gather_pool_seq(q, k_v, v_v, tables, None, None), pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    merged = A.paged_decode(q, kp, vp, tables, pos, impl="xla",
                            layer=jnp.int32(2))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
