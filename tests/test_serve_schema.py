"""Operand-schema registry pins (ISSUE 20): the declarative source of
truth in ``mxnet_tpu/serve/schema.py`` must keep producing EXACTLY the
positional facts the pre-refactor engine hand-counted — donation index
pairs, the 29-byte slot-state total, meta-row layouts — and its
build-time validation must refuse a drifted signature instead of
letting XLA donate the wrong buffer (the PR-18 recycled-page shape).
"""
import pytest

from mxnet_tpu.serve import schema

# the hand-counted literals the five jits carried before the registry
# landed — the refactor must be a pure re-derivation, not a re-pricing
_PRE_REFACTOR_DONATE = {
    "step": (5, 6),
    "admit": (6, 7),
    "hit": (5, 6),
    "chunk": (8, 9),
    "verify": (7, 8),
}
_PRE_REFACTOR_ARITY = {
    "step": 14, "admit": 15, "hit": 14, "chunk": 17, "verify": 16,
}


def _fn_with(params):
    ns = {}
    exec("def f({}):\n    return None".format(", ".join(params)), ns)
    return ns["f"]


class TestRegistryPins:
    def test_executable_set_is_the_five_pool_programs(self):
        assert set(schema.executable_names()) == {
            "step", "admit", "hit", "chunk", "verify"}

    def test_donate_indices_match_pre_refactor_literals(self):
        for name, want in _PRE_REFACTOR_DONATE.items():
            assert schema.donate_argnums(name) == want, name

    def test_arities_match_pre_refactor_signatures(self):
        for name, want in _PRE_REFACTOR_ARITY.items():
            assert schema.arity(name) == want, name

    def test_every_executable_donates_exactly_the_kv_pools(self):
        for name in schema.executable_names():
            assert schema.donated_operands(name) == ("kp", "vp"), name

    def test_state_operands_are_the_nine_pool_columns(self):
        assert schema.state_operands() == (
            "kp", "vp", "pos", "tok", "active", "stop", "keys", "dl",
            "spec")
        assert schema.state_arity() == 9
        # every executable's operand list ENDS with the state tuple —
        # the *state splat at dispatch sites depends on it
        for name in schema.executable_names():
            assert schema.operands(name)[-9:] == schema.state_operands()

    def test_slot_state_prices_to_29_bytes(self):
        assert schema.slot_state_bytes() == 29

    def test_unknown_executable_is_an_error(self):
        with pytest.raises(ValueError):
            schema.operands("prefill")


class TestJitDonateValidation:
    def test_matching_signature_yields_registry_indices(self):
        for name in schema.executable_names():
            fn = _fn_with(schema.operands(name))
            assert schema.jit_donate(name, fn) == \
                _PRE_REFACTOR_DONATE[name], name

    def test_inserted_operand_without_schema_update_raises(self):
        """The PR-18 shape at build time: a parameter lands in the
        signature, the schema does not move, and the derivation refuses
        to hand XLA a donation map it cannot vouch for."""
        params = list(schema.operands("admit"))
        params.insert(2, "scratch_rows")
        with pytest.raises(ValueError, match="drifted"):
            schema.jit_donate("admit", _fn_with(params))

    def test_dropped_operand_raises(self):
        params = [p for p in schema.operands("step") if p != "sw"]
        assert len(params) == schema.arity("step") - 1
        with pytest.raises(ValueError, match="drifted"):
            schema.jit_donate("step", _fn_with(params))

    def test_renamed_donated_operand_raises(self):
        params = [("kpages" if p == "kp" else p)
                  for p in schema.operands("verify")]
        with pytest.raises(ValueError, match="drifted"):
            schema.jit_donate("verify", _fn_with(params))


class TestMetaLayouts:
    def test_widths_match_pre_refactor_row_shapes(self):
        assert schema.meta_width("admit") == 6
        assert schema.meta_width("hit") == 7
        assert schema.meta_width("chunk") == 8
        assert schema.meta_width("step") == 0
        assert schema.meta_width("verify") == 0

    def test_meta_row_roundtrips_through_meta_col(self):
        fields = schema.meta_fields("admit")
        vals = {f: i * 10 for i, f in enumerate(fields)}
        row = schema.meta_row("admit", **vals)
        assert len(row) == schema.meta_width("admit")
        for f in fields:
            assert row[schema.meta_col("admit", f)] == vals[f]

    def test_meta_cols_is_the_full_index_map(self):
        cols = schema.meta_cols("chunk")
        assert set(cols) == set(schema.meta_fields("chunk"))
        assert sorted(cols.values()) == list(
            range(schema.meta_width("chunk")))

    def test_meta_row_missing_field_raises(self):
        vals = {f: 0 for f in schema.meta_fields("hit")[1:]}
        with pytest.raises(ValueError):
            schema.meta_row("hit", **vals)

    def test_meta_row_extra_field_raises(self):
        vals = {f: 0 for f in schema.meta_fields("hit")}
        vals["ttl"] = 3
        with pytest.raises(ValueError):
            schema.meta_row("hit", **vals)

    def test_unknown_meta_field_raises(self):
        with pytest.raises(ValueError):
            schema.meta_col("admit", "ttl")


class TestKvPagePricing:
    def test_int8_page_bytes_formula(self):
        # codes: NL * 2 * KV * page * D int8 + per-page scales:
        # NL * 2 * KV * float32 — the ledger's resident-page price
        nl, kv, page, d = 4, 2, 16, 64
        assert schema.kv_page_int8_bytes(nl, kv, page, d) == \
            2 * nl * kv * (page * d * 1 + 4)

    def test_kv_dtype_pins_match_decoding(self):
        """decoding.py cannot import serve (cycle), so it carries its
        own dtype constants — these pins are the contract that they
        stay in lockstep with the schema's declaration."""
        jnp = pytest.importorskip("jax.numpy")
        from mxnet_tpu.models import decoding
        assert jnp.dtype(decoding._KV_CODE_DTYPE).name == \
            schema.KV_PAGE_INT8["codes"]
        assert jnp.dtype(decoding._KV_SCALE_DTYPE).name == \
            schema.KV_PAGE_INT8["scales"]
        scale_bytes = jnp.dtype(decoding._KV_SCALE_DTYPE).itemsize
        assert schema.kv_page_int8_bytes(1, 1, 1, 1) == \
            2 * (1 + scale_bytes)


class TestPoolTables:
    """The declared row kinds and their tables (ISSUE 33: the slot table,
    one entry a slot beside its pages)."""

    def test_declared_kinds_and_tables(self):
        assert schema.POOL_TABLES == ("main", "window", "slot")
        assert {k: v["table"] for k, v in schema.POOL_ROWS.items()} == {
            "kv": "main", "latent_index": "main", "latent": "main",
            "latent_window": "window", "kv_window": "window",
            "ssm_state": "slot", "retention_state": "slot"}
        assert schema.pool_rows("kv_window") == ("window", ("k", "v"))
        assert schema.pool_rows("ssm_state") == (
            "slot", ("state", "conv_tail"))
        assert schema.pool_rows("retention_state") == (
            "slot", ("state", "z"))
        assert all(v["table"] in schema.POOL_TABLES
                   for v in schema.POOL_ROWS.values())

    def test_unknown_kind_names_the_declared_ones(self):
        with pytest.raises(ValueError, match="ssm_state"):
            schema.pool_rows("lstm_state")

    @pytest.mark.parametrize("width,lanes", [(4352, 4352), (80, 128),
                                             (128, 128), (8512, 8576)])
    def test_slot_rows_are_whole_lane_tiles(self, width, lanes):
        assert schema.row_lanes(width) == lanes

    @pytest.mark.parametrize("name", ["step", "admit", "chunk"])
    def test_slot_arrays_are_donated_by_position(self, name):
        """The slot-table arrays ride in ``vp``: the donation positions the
        literals give are where the engine's state tuple puts them."""
        ops = schema.operands(name)
        at = schema.donate_argnums(name)
        assert [ops[i] for i in at] == ["kp", "vp"]
        assert at[1] == len(ops) - schema.state_arity() + 1
