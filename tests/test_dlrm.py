"""DLRM and the values it reads, on the CPU at toy size: the parsers keep a
numeric field's value under hashing and nothing else's, the compact and the
dictionary wire and a packed-v2 record carry the values as one plane that is
absent without numeric fields, the program agrees with the benchmark's plain
reference (benchmarks/reference/dlrm_criteo.py) on every wire, and an exported
model scores rows with values as the trainer does.  What holds the products to
float32 ON THE TPU is the lowered text below and the cell's own check on the
chip (PERF.md section 2)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.generators.rows import RowSpec
from benchmarks.generators.rows_values import ValueRowGenerator
from benchmarks.harness import manifest, refcheck
from benchmarks.reference import dlrm_criteo
from xflow_tpu.config import Config, mlp_widths
from xflow_tpu.io import packed
from xflow_tpu.io.batch import (
    make_batch, numeric_plane, values_fit_plane, values_from_plane,
)
from xflow_tpu.io.compact import CompactBatch, plane_specs
from xflow_tpu.io.libffm import parse_block
from xflow_tpu.io.loader import ShardLoader, make_parse_fn
from xflow_tpu.models import blocks, make_model
from xflow_tpu.optim import make_optimizer
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import (
    TrainStep, compact_wire_np, init_state, values_from_numeric,
)

NUMERIC = 3
# three numeric fields, four categorical (3..6) and z in the last bucket's
# place: five interacting vectors, ten pairs
DLRM = {
    "model": "dlrm", "emb_dim": dlrm_criteo.EMB_DIM, "numeric_fields": NUMERIC,
    "max_fields": 8, "mlp_bottom": "16-128", "mlp_top": "32-16",
    "v_init_scale": 0.3, "sgd_lr": 0.05,
}
VALUES = {"fields": 13, "count_zipf_a": 1.2, "count_max": 65535, "u_scale": 0.22}


def _config(**fields) -> Config:
    return Config(**{
        "optimizer": "ftrl", "table_size_log2": 12, "batch_size": 64,
        "max_nnz": 6, "hot_size_log2": 5, "hot_nnz": 6, "num_devices": 1,
        "seed": 3, **DLRM, **fields,
    })


def _batches(cfg: Config, count: int = 3, seed: int = 5) -> list:
    """Batches with a hot section, padding entries and examples: every row
    has one entry of each numeric field (a head row, a value in [0, 4)),
    and categorical entries of fields 3 to 8, 7 and 8 outside the model's."""
    rng = np.random.default_rng(seed)
    shape = (cfg.batch_size, cfg.max_nnz + cfg.hot_nnz)
    out = []
    for _ in range(count):
        keys = rng.integers(0, cfg.table_size, shape)
        keys = np.where(rng.random(shape) < 0.5, rng.integers(0, 40, shape), keys)
        mask = (rng.random(shape) < 0.8).astype(np.float32)
        slots = rng.integers(NUMERIC, 9, shape)
        slots[:, :NUMERIC] = np.arange(NUMERIC)
        keys[:, :NUMERIC] = np.arange(NUMERIC)
        vals = np.ones(shape, np.float32)
        vals[:, :NUMERIC] = np.log1p(
            rng.integers(0, 50, (cfg.batch_size, NUMERIC))
        ).astype(np.float32)
        weights = np.ones(cfg.batch_size, np.float32)
        weights[-5:] = 0.0
        out.append(make_batch(
            keys.astype(np.int32), slots.astype(np.int32), vals * mask, mask,
            rng.integers(0, 2, cfg.batch_size).astype(np.float32), weights,
            cfg.hot_size, cfg.hot_nnz,
        ))
    return out


def _system(cfg: Config):
    mesh = make_mesh(1)
    model, opt = make_model(cfg), make_optimizer(cfg)
    return types.SimpleNamespace(
        step=TrainStep(model, opt, cfg, mesh),
        state=init_state(model, opt, cfg, mesh),
    )


# -- the model against the plain reference -------------------------------------


@pytest.mark.parametrize("fields, wire", [
    ({"hot_impl": "mxu"}, "dict"),
    ({"hot_impl": "seg"}, "dict"),
    ({"wire_dedup": "off", "hot_impl": "mxu"}, "compact"),
    ({"wire_mode": "full", "hot_impl": "mxu"}, "full"),
    ({"hot_size_log2": 0, "hot_nnz": 0}, "dict"),
], ids=lambda v: "-".join(map(str, v.values())) if isinstance(v, dict) else v)
def test_program_steps_agree_with_the_dlrm_reference(fields, wire):
    """Three FTRL + SGD steps of the program against the reference, under the
    limits that decide ``correct`` in ``dlrm_tb.train_packed``, with the
    values crossing on each wire: rows, logloss and all ten dense arrays."""
    cfg = _config(**fields)
    system = _system(cfg)
    assert system.step.wire_format == wire
    got = refcheck.check_train_steps(system, dlrm_criteo, _batches(cfg), cfg)
    assert got["ok"], got["steps"]
    for step in got["steps"]:
        assert set(step["dense"]) == {
            "bot_w1", "bot_b1", "bot_w2", "bot_b2", "top_w1", "top_b1",
            "top_w2", "top_b2", "w_out", "b_out",
        }
        # the values reach the bottom stack: its first matrix moves
        assert step["dense"]["bot_w1"]["update_ulps"] > 100


def _model_inputs(cfg: Config, seed: int = 0):
    model = make_model(cfg)
    rng = np.random.default_rng(seed)
    b, k = 16, 10
    slots = rng.integers(0, 9, (b, k)).astype(np.int32)
    mask = (rng.random((b, k)) < 0.85).astype(np.float32)
    vals = np.where(slots < NUMERIC, rng.random((b, k)) * 4, 1.0).astype(np.float32)
    rows = rng.normal(size=(b, k, cfg.emb_dim)).astype(np.float32) * 0.3
    dense = model.dense_init(jax.random.PRNGKey(seed))
    return model, jnp.asarray(rows), vals * mask, mask, slots, dense


def test_logit_and_gradients_equal_the_references_on_seeded_weights():
    """One forward and one backward, no optimizer: the logit, the gradient of
    every gathered row (0 for a numeric field's and for an ignored field's)
    and of every dense array."""
    cfg = _config()
    model, rows, x, mask, slots, dense = _model_inputs(cfg)
    batch = {"vals": jnp.asarray(x), "mask": jnp.asarray(mask), "slots": jnp.asarray(slots)}

    def ours(rows, dense):
        return model.logit({"emb": rows}, batch, dense)

    def theirs(rows, dense):
        return dlrm_criteo.logit({"emb": rows}, jnp.asarray(x), slots, cfg.max_fields, dense)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(rows, dense), theirs(rows, dense), rtol=2e-5, atol=2e-5)
        mine = jax.grad(lambda r, d: jnp.sum(jnp.sin(ours(r, d))), (0, 1))(rows, dense)
        want = jax.grad(lambda r, d: jnp.sum(jnp.sin(theirs(r, d))), (0, 1))(rows, dense)
    np.testing.assert_allclose(mine[0], want[0], rtol=1e-4, atol=1e-5)
    for name in dense:
        np.testing.assert_allclose(mine[1][name], want[1][name], rtol=1e-4, atol=1e-5)
    unread = (slots < NUMERIC) | (slots >= cfg.max_fields - 1) | (mask == 0)
    assert not np.asarray(mine[0])[unread].any()
    assert np.asarray(mine[0])[~unread].any()


def test_a_missing_numeric_field_reads_zero_and_a_missing_embedding_adds_nothing():
    cfg = _config()
    model, rows, x, mask, slots, dense = _model_inputs(cfg)
    batch = {"vals": jnp.asarray(x), "mask": jnp.asarray(mask), "slots": jnp.asarray(slots)}
    base = model.logit({"emb": rows}, batch, dense)
    # an entry of value 0 is an entry that is not there
    gone = x * (slots != 1)
    dropped = {**batch, "vals": jnp.asarray(gone), "mask": jnp.asarray(mask * (slots != 1))}
    zeroed = {**batch, "vals": jnp.asarray(gone)}
    np.testing.assert_array_equal(
        model.logit({"emb": rows}, dropped, dense), model.logit({"emb": rows}, zeroed, dense)
    )
    # the last bucket is z's: an entry of field max_fields - 1 changes nothing
    moved = rows.at[:, :, :].set(jnp.where((slots == 7)[..., None], 9.0, rows))
    np.testing.assert_array_equal(model.logit({"emb": moved}, batch, dense), base)


@pytest.mark.parametrize("n, d", [(5, 8), (27, 128)])
def test_pairwise_dots_are_the_lower_triangle(n, d):
    rng = np.random.default_rng(n)
    t = rng.normal(size=(6, n, d)).astype(np.float32)
    i, j = np.tril_indices(n, -1)
    want = np.einsum("bid,bjd->bij", t.astype(np.float64), t.astype(np.float64))[:, i, j]
    assert want.shape == (6, blocks.vector_pairs(n))
    with jax.default_matmul_precision("highest"):
        got = blocks.pairwise_dots(jnp.asarray(t))
        grad = jax.grad(lambda t: jnp.sum(blocks.pairwise_dots(t) ** 2))(jnp.asarray(t))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    sym = np.zeros((6, n, n))
    sym[:, i, j] = 2 * want
    sym = sym + sym.transpose(0, 2, 1)
    np.testing.assert_allclose(grad, sym @ t.astype(np.float64), rtol=1e-4, atol=1e-3)


def test_pairwise_dots_ask_for_float32_and_open_their_scope():
    """On the TPU a default-precision dot rounds both operands to bfloat16;
    the lowered interaction asks for HIGHEST, under ``xf.interact``."""
    lowered = jax.jit(blocks.pairwise_dots).lower(jnp.zeros((4, 27, 128), jnp.float32))
    dots = [l for l in lowered.as_text().splitlines() if "dot_general" in l]
    assert dots and all("precision = [HIGHEST, HIGHEST]" in l for l in dots)
    assert "xf.interact" in lowered.as_text(debug_info=True)


def test_the_stack_init_takes_uneven_widths_and_keeps_the_even_draw():
    key = jax.random.PRNGKey(4)
    even = blocks.mlp_stack_init(key, 12, 8, layers=3)
    as_widths = blocks.mlp_stack_init(key, 12, (8, 8, 8))
    assert all((even[k] == as_widths[k]).all() for k in even)
    uneven = blocks.mlp_stack_init(key, 13, (512, 256, 128), prefix="bot_")
    assert {k: v.shape for k, v in uneven.items()} == {
        "bot_w1": (13, 512), "bot_b1": (512,), "bot_w2": (512, 256),
        "bot_b2": (256,), "bot_w3": (256, 128), "bot_b3": (128,),
    }
    h = blocks.mlp_stack(uneven, jnp.ones((2, 13)), 3, prefix="bot_")
    assert h.shape == (2, 128)


def test_the_criteo_terabyte_shapes_are_the_published_ones():
    doc = manifest.config_file("benchmarks/configs/dlrm_ftrl_criteo_tb.json")
    cfg = Config(**{k: v for k, v in doc.items() if k not in manifest.CONFIG_META | {"rehearsal"}})
    model = make_model(cfg)
    shapes = jax.eval_shape(model.dense_init, jax.random.PRNGKey(0))
    assert (model.vectors, blocks.vector_pairs(model.vectors), model.top_in) == (27, 351, 479)
    assert sum(a.size for a in shapes.values()) == 2_368_897
    per_example = sum(k * n for k, n in model.dense_matmuls())
    assert per_example == 170_496 + 2_194_688 + 93_312
    assert dlrm_criteo.matmuls({k: a.shape for k, a in shapes.items()}) == [
        (13, 512), (512, 256), (256, 128), (479, 1024), (1024, 1024),
        (1024, 512), (512, 256), (256, 1),
    ]
    assert [spec.dim for spec in model.tables()] == [128]


@pytest.mark.parametrize("fields, match", [
    ({"numeric_fields": 0}, "numeric_fields must be >= 1"),
    ({"numeric_fields": 7}, "at least 2"),
    ({"mlp_bottom": "16-64"}, "ends in 64"),
    ({"mlp_bottom": "16-x"}, "dash-separated"),
    ({"mlp_top": ""}, "dash-separated"),
    ({"numeric_fields": 256}, r"\[0, 255\]"),
])
def test_config_refuses_shapes_the_model_cannot_have(fields, match):
    with pytest.raises(ValueError, match=match):
        _config(**fields)
    assert mlp_widths("1024-1024-512-256") == (1024, 1024, 512, 256)


def test_train_cli_takes_the_three_fields():
    from xflow_tpu import train

    args = train.build_parser().parse_args([
        "--model", "dlrm", "--numeric-fields", "13", "--mlp-bottom", "512-256-128",
        "--mlp-top", "1024-1024-512-256", "--train", "x",
    ])
    assert (args.model, args.numeric_fields, args.mlp_bottom, args.mlp_top) == (
        "dlrm", 13, "512-256-128", "1024-1024-512-256"
    )


# -- the parsers ---------------------------------------------------------------

LINES = (
    b"1\t0:a:0.5 1:b:2.25 2:c:7 5:d:3.5 12:e:1e-3\n"
    b"0 0:a:-1.5 1:b:nan 2:c:1e39 3:x:junk 1:q:0x10\n"
    b"1 2:only:6.02e23 -1:neg:4 0:a: 7:v:\n"
)


@pytest.mark.parametrize("numeric", [0, 2, 13])
def test_parsers_agree_on_numeric_values_and_leave_every_other_token_one(numeric):
    """Under hash_mode a token of a field below ``numeric_fields`` keeps its
    value (a malformed or non-finite one skips the token, as numeric mode's);
    every other token is 1 whatever it says, and 0 fields is the reference's
    loader."""
    from xflow_tpu import native

    assert native.available(), "the native parser did not build"
    mine = parse_block(LINES, 1 << 16, True, 9, numeric)
    theirs = make_parse_fn(1 << 16, True, 9, numeric_fields=numeric)(LINES)
    for name in ("labels", "row_ptr", "keys", "slots", "vals"):
        np.testing.assert_array_equal(getattr(mine, name), getattr(theirs, name))
    inside = (mine.slots >= 0) & (mine.slots < numeric)
    assert (mine.vals[~inside] == 1.0).all()
    if numeric == 0:
        assert len(mine.vals) == 14 and (mine.vals == 1.0).all()
        three = make_parse_fn(1 << 16, True, 9)(LINES)  # the three-argument form
        np.testing.assert_array_equal(three.vals, mine.vals)
    if numeric == 2:
        # 1:b:nan, 1:q:0x10 and "0:a:" are skipped; 2:c:1e39 is field 2: kept as 1
        assert mine.vals[inside].tolist() == [0.5, 2.25, -1.5]
        assert len(mine.vals) == 11
    if numeric == 13:
        assert mine.vals[inside].tolist() == [
            0.5, 2.25, 7.0, 3.5, np.float32(1e-3), -1.5, np.float32(6.02e23),
        ]


def test_numeric_mode_is_what_it_was():
    block = parse_block(b"1 0:5:0.5 3:9:2\n", 0, False, 0, 13)
    assert block.vals.tolist() == [0.5, 2.0] and block.keys.tolist() == [5, 9]


# -- the values plane on the wires and in a packed record ----------------------


def test_the_plane_holds_the_numeric_values_and_nothing_else_fits_it():
    cfg = _config()
    batch = _batches(cfg, 1)[0]
    plane = numeric_plane(batch, NUMERIC)
    assert plane.shape == (cfg.batch_size, NUMERIC) and plane.dtype == np.float32
    assert values_fit_plane(batch, NUMERIC) and not values_fit_plane(batch, 0)
    for slots, vals, mask in (
        (batch.slots, batch.vals, batch.mask), (batch.hot_slots, batch.hot_vals, batch.hot_mask),
    ):
        np.testing.assert_array_equal(values_from_plane(slots, mask, plane), vals * mask)
        np.testing.assert_array_equal(
            values_from_numeric(jnp.asarray(plane), jnp.asarray(slots), jnp.asarray(mask)),
            vals * mask,
        )
    # a value on an entry outside the numeric fields has no place in the plane
    off = _batches(cfg, 1)[0]
    r, c = np.nonzero((off.mask > 0) & (off.slots >= NUMERIC))
    off.vals[r[0], c[0]] = 2.0
    assert not values_fit_plane(off, NUMERIC)
    with pytest.raises(ValueError, match="binary features"):
        CompactBatch.from_batch(off, cfg.table_size, cfg.hot_size, numeric_fields=NUMERIC)
    # nor do two values of one row and field
    two = _batches(cfg, 1)[0]
    two.slots[0, :2], two.mask[0, :2], two.vals[0, :2] = 1, 1.0, (0.25, 0.75)
    assert not values_fit_plane(two, NUMERIC)


def test_a_batch_round_trips_through_the_dictionary_wires_planes():
    cfg = _config()
    batch = _batches(cfg, 1)[0]
    cb = CompactBatch.from_batch(batch, cfg.table_size, cfg.hot_size, numeric_fields=NUMERIC)
    back = cb.expand()
    for name in ("keys", "slots", "mask", "hot_keys", "hot_slots", "hot_mask", "labels", "weights"):
        np.testing.assert_array_equal(getattr(back, name), getattr(cb.expand(), name))
    # entries re-compact leftward; their values go with them
    assert sorted(back.vals[back.mask > 0].tolist()) == sorted(batch.vals[batch.mask > 0].tolist())
    np.testing.assert_array_equal(numeric_plane(back, NUMERIC), numeric_plane(batch, NUMERIC))
    wire = cb.wire(ship_slots=False)  # the slots ship with the plane regardless
    assert wire["cw_nv"].shape == (cfg.batch_size, NUMERIC) and "cw_cs" in wire
    plain = CompactBatch.from_batch(
        dataclass_replace_values(batch), cfg.table_size, cfg.hot_size
    )
    assert plain.nv is None and "cw_nv" not in plain.wire(True)
    assert cb.wire_nbytes(True) - plain.wire_nbytes(True) == 4 * NUMERIC * cfg.batch_size


def dataclass_replace_values(batch):
    """``batch`` with every value 1: what a parser without numeric fields
    makes of the same text."""
    import dataclasses

    return dataclasses.replace(batch, vals=batch.mask.copy(), hot_vals=batch.hot_mask.copy())


@pytest.mark.parametrize("fields, plane", [
    ({}, "cw_nv"), ({"wire_dedup": "off"}, "nvals"), ({"wire_mode": "full"}, None),
])
def test_the_device_sees_the_values_the_host_had(fields, plane):
    """put_batch -> the program's decode -> the model's view: ``vals`` holds
    each entry's value on every wire, and the wire row books 4 bytes a
    numeric field and example where a plane shipped."""
    from xflow_tpu.obs import Obs

    cfg = _config(**fields)
    system = _system(cfg)
    system.step.obs = obs = Obs()
    batch = _batches(cfg, 1)[0]
    arrays = system.step.put_batch(batch)
    assert (plane in arrays) if plane else not {"cw_nv", "nvals"} & set(arrays)
    view = jax.jit(lambda a: system.step._model_view(system.step._expand_wire(a)))(arrays)
    got = np.asarray(view["vals"] * view["mask"])
    want = np.concatenate([batch.hot_vals * batch.hot_mask, batch.vals * batch.mask], axis=1)
    assert sorted(got.ravel().tolist()) == sorted(want.ravel().tolist())
    picked = np.asarray(view["vals"])[np.asarray(view["slots"] == 1) & (np.asarray(view["mask"]) > 0)]
    assert set(picked.tolist()) <= set(numeric_plane(batch, NUMERIC)[:, 1].tolist())
    booked = obs.registry.snapshot().counters.get("wire.values_bytes", 0)
    assert booked == (4 * NUMERIC * cfg.batch_size if plane else 0)


def _text_shard(tmp_path, rows: int = 300, seed: int = 21):
    spec = RowSpec(int_vocab=1, cat_vocab_max=5000)
    gen = ValueRowGenerator(spec, seed, VALUES)
    gid, labels = gen.draw(rows, (0, 0))
    path = str(tmp_path / "rows-00000")
    with open(path, "wb") as f:
        f.write(gen.text(gid, labels))
    return gen, gid, labels, path


def test_a_packed_v2_shard_carries_the_values_the_text_held(tmp_path):
    gen, gid, _, src = _text_shard(tmp_path)
    geometry = dict(batch_size=64, max_nnz=40, table_size=1 << 14, hash_seed=21)
    dst = str(tmp_path / "rows.pk")
    meta = packed.convert_shard(src, dst, fmt="v2", numeric_fields=13, **geometry)
    assert meta["numeric_fields"] == 13 and meta["examples"] == 300
    with open(dst, "rb") as f:
        assert packed.read_header(f)[0]["numeric_fields"] == 13
    want = gen.values(gid)
    # a reader that says nothing of numeric fields takes what the records hold
    for loader_fields in ({}, {"numeric_fields": 13}):
        loader = ShardLoader(dst, **geometry, **loader_fields)
        seen = np.concatenate([
            numeric_plane(b, 13)[: int(b.weights.sum())] for b, _ in loader.iter_batches()
        ])
        np.testing.assert_array_equal(seen, want)
    compact = ShardLoader(dst, emit_compact=True, **geometry)
    first = next(iter(compact.iter_batches()))[0]
    assert isinstance(first, CompactBatch)
    np.testing.assert_array_equal(first.nv, want[:64])
    with pytest.raises(ValueError, match="numeric_fields=13"):
        list(ShardLoader(dst, numeric_fields=0, **geometry).iter_batches())


def test_a_shard_packed_without_numeric_fields_is_the_pre_pr_shard(tmp_path):
    """Its header has no ``numeric_fields`` key and its records no plane:
    byte for byte what the converter wrote before the key existed, read by a
    loader that says 0 and refused by one that says 13."""
    _, _, _, src = _text_shard(tmp_path)
    geometry = dict(batch_size=64, max_nnz=40, table_size=1 << 14, hash_seed=21)
    dst = str(tmp_path / "plain.pk")
    packed.convert_shard(src, dst, fmt="v2", **geometry)
    with open(dst, "rb") as f:
        header, start = packed.read_header(f)
        body = f.read()
    assert set(header) == {
        "version", "batch_size", "cold_nnz", "hot_nnz", "hot_size", "table_size",
        "hash_mode", "hash_seed", "remap_sha256", "dict_cap", "granule_div",
        "granule_min", "key_bytes", "hx16", "batches", "examples",
    }
    batches = [b for b, _ in ShardLoader(dst, numeric_fields=0, **geometry).iter_batches()]
    assert all((b.vals == b.mask).all() for b in batches)
    cb = CompactBatch.from_batch(batches[0], 1 << 14, 0, strict_layout=True)
    assert cb.nv is None
    record = sum(
        int(np.prod(shape)) * dtype.itemsize
        for _, shape, dtype in plane_specs(
            batch_size=64, cold_nnz=40, hot_nnz_cap=0, key_bytes=3, hx16=False,
            slots_code=cb.slots_code, n_cold=cb.n_cold, n_dict=cb.n_dict,
            n_dict_occ=cb.n_dict_occ, n_hot=0, n_h8=0,
        )
    )
    first_len = packed._REC_HEADER.unpack_from(body, 0)[7]
    assert first_len == packed._REC_HEADER.size + record
    with pytest.raises(ValueError, match="numeric_fields=0"):
        list(ShardLoader(dst, numeric_fields=13, **geometry).iter_batches())


EXISTING = sorted(
    c["name"] for c in manifest.load()["configs"] if c["name"] != "dlrm_ftrl_criteo_tb"
)
PRE_PR_PLANES = {
    "cw_cu", "cw_cun", "cw_ci", "cw_ct", "cw_cf", "cw_cc", "cw_lb", "cw_wb",
    "cw_h8", "cw_hx", "cw_hxh", "cw_hf", "cw_hc", "cw_cs", "cw_hs",
    "ckeys", "labels_u8", "weights_u8", "slots_u8", "hot_ckeys_u16",
    "hot_ckeys", "hot_slots_u8",
}


@pytest.mark.parametrize("config", EXISTING)
def test_an_existing_configuration_ships_the_planes_it_shipped(config):
    """No configuration of the benchmark but DLRM's states ``numeric_fields``:
    its batches ship no values plane on either packed wire, its bytes an
    example are the sum of the planes it always shipped, and its wire row has
    no ``values_bytes_per_example``."""
    from xflow_tpu.obs import Obs

    doc = manifest.apply_rehearsal(manifest.config_file(f"benchmarks/configs/{config}.json"), True)
    cfg = Config(**{
        **{k: v for k, v in doc.items() if k not in manifest.CONFIG_META},
        "num_devices": 1, "table_shards": 0,
    })
    assert cfg.numeric_fields == 0
    rng = np.random.default_rng(1)
    shape = (cfg.batch_size, cfg.max_nnz + cfg.hot_nnz)
    mask = (rng.random(shape) < 0.6).astype(np.float32)
    batch = make_batch(
        rng.integers(0, cfg.table_size, shape).astype(np.int32),
        rng.integers(0, 39, shape).astype(np.int32), mask.copy(), mask,
        rng.integers(0, 2, cfg.batch_size).astype(np.float32),
        np.ones(cfg.batch_size, np.float32), cfg.hot_size, cfg.hot_nnz,
    )
    step = TrainStep(make_model(cfg), make_optimizer(cfg), cfg, make_mesh(1))
    step.obs = obs = Obs()
    wire, cb = step.host_wire_np(batch)
    assert set(wire) <= PRE_PR_PLANES and cb.nv is None
    assert set(compact_wire_np(batch, step._ship_slots, step._hot_u16)) <= PRE_PR_PLANES
    step.put_batch(batch)
    counters = obs.registry.snapshot().counters
    assert counters["wire.bytes"] == sum(v.nbytes for v in wire.values())
    assert "wire.values_bytes" not in counters


# -- train, checkpoint, export, serve -----------------------------------------


@pytest.fixture(scope="module")
def dlrm_trained(tmp_path_factory):
    """A DLRM trained for two epochs from libffm text whose numeric fields
    carry values, through ``Trainer`` as every family goes (hot remap, text
    loader, staging ring), and its exported artifact."""
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.trainer import Trainer

    root = tmp_path_factory.mktemp("dlrm")
    gen, gid, labels, path = _text_shard(root, rows=512, seed=33)
    cfg = Config(
        model="dlrm", emb_dim=8, numeric_fields=13, max_fields=40,
        mlp_bottom="16-8", mlp_top="32-16", sgd_lr=0.01, v_init_scale=0.1,
        train_path=str(root / "rows"), test_path=str(root / "rows"),
        epochs=2, batch_size=64, table_size_log2=14, max_nnz=40,
        hot_size_log2=6, hot_nnz=16, freq_sample_mib=1, num_devices=1,
        checkpoint_dir=str(root / "ckpt"), seed=33,
    )
    trainer = Trainer(cfg)
    trainer.train()
    art = str(root / "artifact")
    export_artifact(trainer, art)
    return {"trainer": trainer, "artifact": art, "text": path, "gen": gen, "gid": gid}


def test_trainer_reads_the_values_through_the_text_path(dlrm_trained):
    trainer = dlrm_trained["trainer"]
    loader = trainer._loader(dlrm_trained["text"])
    batch = next(iter(loader.iter_batches()))[0]
    want = dlrm_trained["gen"].values(dlrm_trained["gid"])[:64]
    np.testing.assert_array_equal(numeric_plane(batch, 13), want)
    assert trainer.step.wire_format == "dict" and trainer.epoch == 2


def test_an_exported_dlrm_scores_rows_with_values_as_the_trainer_does(dlrm_trained, tmp_path):
    """export -> PredictEngine: the engine's featurize goes through the same
    parser (``numeric_fields`` rides in the artifact's configuration), its
    packed put ships the values plane, and its scores are the trainer's; with
    the values struck from the text they are not."""
    from xflow_tpu.serve.engine import PredictEngine

    trainer = dlrm_trained["trainer"]
    engine = PredictEngine.load(dlrm_trained["artifact"], buckets=(8, 64), warm=True)
    assert engine.cfg.numeric_fields == 13 and engine.step.compact_wire
    pred = tmp_path / "pred.txt"
    trainer.evaluate(pred_out=str(pred))
    want = np.asarray([float(l.split("\t")[1]) for l in pred.read_text().splitlines()])
    lines = open(dlrm_trained["text"]).read().splitlines()
    got = engine.score_text(lines)
    np.testing.assert_allclose(got, want, atol=1e-6)
    import re

    binary = [re.sub(r"(\d\d:\d{10}):[0-9.]+", r"\1:1", line) for line in lines]
    assert np.abs(engine.score_text(binary) - want).max() > 1e-3


def test_a_dlrm_checkpoint_restores_to_the_same_state(dlrm_trained):
    """``Trainer.train()`` saved the state at its end: a second trainer over
    the same directory restores the table, its FTRL state and all ten dense
    arrays."""
    from xflow_tpu.trainer import Trainer

    trainer = dlrm_trained["trainer"]
    again = Trainer(trainer.cfg)
    try:
        cursor = again.restore()
        assert cursor is not None and cursor["epoch"] == 2
        for name, array in trainer.state["dense"].items():
            np.testing.assert_array_equal(again.state["dense"][name], array)
        for name in ("param", "n", "z"):
            np.testing.assert_array_equal(
                again.state["tables"]["emb"][name], trainer.state["tables"]["emb"][name]
            )
    finally:
        again.close()


# -- the benchmark's side, counted in tier-1 -----------------------------------
# ``benchmarks/tests/`` is the harness's own suite and tier-1 does not run it;
# the new cell's generator, driver, readers and rehearsal are held here by the
# same cases, imported and collected as this module's.

from benchmarks.tests.test_dlrm import (  # noqa: E402, F401
    test_first_batches_of_the_packed_shards_carry_the_values_the_text_held,
    test_the_generator_draws_the_stock_ids_and_seed_pure_values,
    test_the_manifest_resolves_the_cells_files,
    test_the_new_readers_on_a_fixture,
    test_the_reference_declares_the_stacks_and_the_output,
    test_the_rehearsal_of_the_cell_is_correct_and_its_control_fails,
    test_values_are_printed_so_that_they_read_back_exactly,
)
