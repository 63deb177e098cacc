"""A source's padded columns are built once, on the host, at their final
shape `[n_dev * cap]`, and `shard.put` is their one crossing (PR 34):
`_shard_table` hands on numpy arrays equal, byte for byte, to what it
used to make by going up through `Batch.from_arrow`, back through
`np.asarray`, and up again; `Batch.from_arrow` itself returns what it
returned; and the stage driver's spans and counters say what crossed."""

import datetime
import decimal
from decimal import Decimal

import jax
import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.columnar import arrow_interop
from auron_tpu.columnar.batch import (
    Batch, DeviceColumn, DeviceStringColumn, bucket_capacity, bucket_width,
)
from auron_tpu.config import conf
from auron_tpu.ir.schema import TypeId, from_arrow_schema
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.mesh import data_mesh

ROWS = 37           # dealt 37 / 19+18 / 10+10+10+7
N_DEVS = (1, 2, 4)
EXACT_BITS = "auron.sort.f64.exactbits"


def _ts(i):
    return datetime.datetime(2001, 2, 3, 4, 5, 6, i)


# name -> (arrow type, value of row i, options in force); nulls in every
# one unless its name says otherwise
COLUMNS = {
    "int32": (pa.int32(), lambda i: None if i % 5 == 0 else i - 7, {}),
    "int64": (pa.int64(), lambda i: None if i % 7 == 0 else (i - 3) << 40,
              {}),
    "decimal-7-2": (pa.decimal128(7, 2), lambda i: None if i % 4 == 1 else
                    Decimal(i * 1234 - 5000) / 100, {}),
    "decimal-18-4": (pa.decimal128(18, 4), lambda i: None if i % 4 == 2 else
                     Decimal((-1) ** i * (10 ** 17 + i)) / 10 ** 4, {}),
    # NaN, -0.0, a value float32 cannot hold, an infinity
    "double-bits": (pa.float64(), lambda i: (
        None, float("nan"), -0.0, 1 / 3, float("-inf"))[i] if i < 5
        else i * 0.1, {EXACT_BITS: "on"}),
    "double-plain": (pa.float64(), lambda i: None if i % 6 == 0 else 1 / (i + 1),
                     {EXACT_BITS: "off"}),
    "bool": (pa.bool_(), lambda i: None if i % 3 == 0 else i % 2 == 0, {}),
    "date": (pa.date32(), lambda i: None if i % 6 == 2 else
             datetime.date(1998, 1, 1) + datetime.timedelta(days=40 * i), {}),
    "timestamp-us": (pa.timestamp("us"),
                     lambda i: None if i % 6 == 3 else _ts(i), {}),
    # another unit is cast on the way
    "timestamp-ms": (pa.timestamp("ms"), lambda i: None if i % 6 == 4 else
                     _ts(1000 * i), {}),
    "dictionary": (pa.dictionary(pa.int8(), pa.int64()),
                   lambda i: None if i % 5 == 2 else (i % 3) * 1000, {}),
    # the longest value lies in the last rows alone: the shards' own
    # widths differ (8, then 32), the table's is the widest
    "string": (pa.string(), lambda i: None if i % 5 == 3 else
               "" if i % 5 == 1 else "straße"[:i % 7] if i < 30
               else "x" * (i - 10), {}),
    "binary": (pa.binary(), lambda i: None if i % 4 == 0 else
               bytes([i, 0, 255 - i]), {}),
    "all-null": (pa.int64(), lambda i: None, {}),
    "all-null-string": (pa.string(), lambda i: None, {}),
    "no-null": (pa.int32(), lambda i: i * i, {}),
    "no-null-string": (pa.string(), lambda i: "ab" * (i % 3), {}),
}


def _column(name, rows=range(ROWS)):
    """The case's column over the rows numbered `rows`."""
    at, value, _opts = COLUMNS[name]
    if pa.types.is_dictionary(at):
        return pa.array([value(i) for i in rows],
                        at.value_type).dictionary_encode()
    return pa.array([value(i) for i in rows], at)


def _table(name, shape):
    """One column `v` of the case's type, laid out as `shape` says."""
    if shape == "whole":
        return pa.table({"v": _column(name)})
    if shape == "sliced":
        # a non-zero Arrow offset, odd so that no bit-packed byte aligns
        rows = [*range(5), *range(ROWS), *range(4)]
        return pa.table({"v": _column(name, rows)}).slice(5, ROWS)
    if shape == "chunks":
        # many chunks, none of them at a device's boundary
        cuts = [0, 3, 4, 11, 12, 12, 25, 33, ROWS]
        return pa.Table.from_batches([
            pa.record_batch({"v": _column(name, range(a, b))})
            for a, b in zip(cuts, cuts[1:])])
    if shape == "empty":
        return pa.table({"v": _column(name, range(0))})
    if shape == "few":
        # fewer rows than devices: some devices are dealt none
        return pa.table({"v": _column(name, range(3))})
    raise ValueError(shape)


SHAPES = ("whole", "sliced", "chunks", "empty", "few")


def _leaves(col):
    if isinstance(col, DeviceStringColumn):
        return {"data": col.data, "lengths": col.lengths,
                "validity": col.validity}
    assert isinstance(col, DeviceColumn), type(col)
    out = {"data": col.data, "validity": col.validity}
    if col.bits is not None:
        out["bits"] = col.bits
    return out


def _schema(table):
    """The table's schema, a dictionary-encoded column under its values'
    type (`from_arrow_schema` takes no dictionary type: a caller that
    has one names the schema itself)."""
    return from_arrow_schema(pa.schema([
        f.with_type(f.type.value_type) if pa.types.is_dictionary(f.type)
        else f for f in table.schema]))


def _the_parents_way(table, n_dev):
    """What `_shard_table` did before: every device's slice through
    `Batch.from_arrow`, each part back to the host, the strings' widths
    padded to the widest shard's, the parts concatenated."""
    n = table.num_rows
    per_dev = -(-max(n, 1) // n_dev)
    cap = bucket_capacity(per_dev)
    schema = _schema(table)
    batches = []
    for d in range(n_dev):
        chunk = table.slice(d * per_dev, per_dev)
        arrays = [c.combine_chunks() if c.num_chunks else
                  pa.array([], type=c.type) for c in chunk.columns]
        rb = pa.RecordBatch.from_arrays(arrays, schema=table.schema)
        batches.append(Batch.from_arrow(rb, capacity=cap, schema=schema))
    cols = []
    for ci in range(len(schema)):
        parts = [_leaves(b.columns[ci]) for b in batches]
        stacked = {}
        for leaf in parts[0]:
            arrays = [np.asarray(p[leaf]) for p in parts]
            if arrays[0].ndim == 2:
                w = max(a.shape[1] for a in arrays)
                arrays = [np.pad(a, ((0, 0), (0, w - a.shape[1])))
                          for a in arrays]
            stacked[leaf] = np.concatenate(arrays)
        cols.append(stacked)
    live = np.zeros(n_dev * cap, bool)
    for d in range(n_dev):
        live[d * cap: d * cap + min(max(n - d * per_dev, 0), per_dev)] = True
    return cols, live, cap


def _assert_same_bytes(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def _assert_columns_as_the_parent(cols, want_cols):
    assert all(type(x) is np.ndarray for x in jax.tree.leaves(cols))
    assert len(cols) == len(want_cols)
    for col, want in zip(cols, want_cols):
        got = _leaves(col)
        assert sorted(got) == sorted(want)
        for leaf in want:
            _assert_same_bytes(got[leaf], want[leaf])


def _assert_shards_as_the_parent(table, n_dev):
    want_cols, want_live, want_cap = _the_parents_way(table, n_dev)
    schema, cols, live, cap = S._shard_table(table, data_mesh(n_dev), "parts")
    assert schema == from_arrow_schema(table.schema) and cap == want_cap
    # nothing has gone up yet but the 1-byte-a-slot live mask
    assert isinstance(live, jax.Array)
    _assert_same_bytes(np.asarray(live), want_live)
    _assert_columns_as_the_parent(cols, want_cols)
    return cols


@pytest.mark.parametrize("n_dev", N_DEVS)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("name", sorted(set(COLUMNS) - {"dictionary"}))
def test_every_leaf_is_what_the_round_trip_made(name, shape, n_dev):
    with conf.scoped(COLUMNS[name][2]):
        table = _table(name, shape)
        cols = _assert_shards_as_the_parent(table, n_dev)
        if "double" in name:
            assert (cols[0].bits is not None) == (name == "double-bits")
        if name == "string" and shape != "empty":
            # one width, the bucket of the whole table's longest value
            longest = max(len(v.encode()) for v in
                          table.column("v").to_pylist() if v is not None)
            assert cols[0].data.shape[1] == bucket_width(longest)


@pytest.mark.parametrize("n_dev", N_DEVS)
@pytest.mark.parametrize("shape", SHAPES)
def test_dictionary_encoded_ints_through_the_host_half(shape, n_dev):
    """No table with a dictionary type reaches `_shard_table` (its schema
    converts to none); the conversion both consumers share decodes one
    where the caller names the values' type."""
    table = _table("dictionary", shape)
    want_cols, _live, cap = _the_parents_way(table, n_dev)
    [field] = _schema(table)
    col = arrow_interop.arrow_array_to_host_column(
        field.dtype, table.column("v"), cap,
        S._rows_per_device(table.num_rows, n_dev))
    _assert_columns_as_the_parent([col], want_cols)


@pytest.mark.parametrize("n_dev", N_DEVS)
def test_a_table_of_every_type_at_once(n_dev):
    """The columns side by side, as a source has them, many chunks and a
    non-zero offset together."""
    with conf.scoped({EXACT_BITS: "on"}):
        names = sorted(set(COLUMNS) - {"double-plain", "dictionary"})
        rows = [*range(2), *range(ROWS), *range(4)]
        cuts = [0, 9, 10, 30, len(rows)]
        table = pa.Table.from_batches([
            pa.record_batch({n: _column(n, rows[a:b]) for n in names})
            for a, b in zip(cuts, cuts[1:])]).slice(2, ROWS)
        _assert_shards_as_the_parent(table, n_dev)


@pytest.mark.parametrize("n_dev", (1, 4))
@pytest.mark.parametrize("source", ("store_sales", "customer_demographics",
                                    "date_dim", "item", "promotion"))
def test_query_7s_sources_at_rehearsal_scale(source, n_dev, tmp_path):
    from benchmarks.harness import cells, datagen
    from benchmarks.queries import q07
    cell = cells.load_cell("tpcds-sf1.q07")
    cat = datagen.generate(str(tmp_path), {source: q07.SCANS[source]},
                           cell.config["rehearse_rows"],
                           cell.config["data_seed"], 2**31 + 5)
    table = cat.read(source, list(q07.SCANS[source]))
    assert table.num_rows == cell.config["rehearse_rows"][source]
    _assert_shards_as_the_parent(table, n_dev)


def _put(cols, live, n_dev):
    sharded = jax.sharding.NamedSharding(
        data_mesh(n_dev), jax.sharding.PartitionSpec("parts"))
    return jax.tree.map(lambda x: jax.device_put(x, sharded), (cols, live))


@pytest.mark.parametrize("n_dev", N_DEVS)
def test_the_put_places_each_devices_rows_on_it(n_dev):
    """`device_put` under the mesh's sharding of the host's `[n_dev *
    cap]` array: device d holds slots [d * cap, (d + 1) * cap), equal to
    the host's."""
    with conf.scoped({EXACT_BITS: "on"}):
        table = pa.table({n: _column(n) for n in
                          ("int64", "double-bits", "string", "bool")})
        _schema, cols, live, cap = S._shard_table(
            table, data_mesh(n_dev), "parts")
        placed = _put(cols, live, n_dev)
    for host, dev in zip(jax.tree.leaves((cols, live)),
                         jax.tree.leaves(placed)):
        host = np.asarray(host)
        assert dev.dtype == host.dtype and dev.shape == host.shape
        shards = sorted(dev.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        assert len(shards) == n_dev
        assert [s.device for s in shards] == \
            list(data_mesh(n_dev).devices.flat)
        for d, s in enumerate(shards):
            assert np.asarray(s.data).tobytes() == \
                host[d * cap:(d + 1) * cap].tobytes()


WIDE_DECIMALS = [Decimal("12345678901234567890123456.7891"), None,
                 Decimal(-7)] * 4

HOST_COLUMNS = {
    # past the width the device holds, in the last shard alone
    "wide-string": lambda: pa.array(["a", None, "bc"] * 3 + ["x" * 40] * 3),
    "nested": lambda: pa.array([[1, 2], None, []] * 4, pa.list_(pa.int64())),
}


@pytest.mark.parametrize("n_dev", N_DEVS)
@pytest.mark.parametrize("what", sorted(HOST_COLUMNS))
def test_a_host_resident_column_is_still_refused(what, n_dev):
    table = pa.table({"k": pa.array(range(12)), "v": HOST_COLUMNS[what]()})
    with conf.scoped({"auron.string.device.max.width": 32}):
        assert Batch.from_arrow(table).has_host_columns()
        with pytest.raises(S.SpmdUnsupported, match="host-resident"):
            S._shard_table(table, data_mesh(n_dev), "parts")


@pytest.mark.parametrize("n_dev", N_DEVS)
def test_a_wide_decimal_source_is_two_words_a_value(n_dev):
    """Since PR 35 the stage program holds a decimal of 19-38 digits
    (`DeviceDecimal128Column`: numpy leaves here, like every column of
    `_shard_table`), where the serial engine still keeps it on the host."""
    table = pa.table({"k": pa.array(range(12)),
                      "v": pa.array(WIDE_DECIMALS, pa.decimal128(38, 4))})
    assert Batch.from_arrow(table).has_host_columns()
    _schema, cols, _live, cap = S._shard_table(table, data_mesh(n_dev),
                                               "parts")
    v = cols[1]
    assert all(isinstance(x, np.ndarray) for x in (v.hi, v.lo, v.validity))
    per = 12 // n_dev
    for i, want in enumerate(WIDE_DECIMALS):
        at = (i // per) * cap + i % per
        assert bool(v.validity[at]) == (want is not None)
        got = (int(v.hi[at]) << 64) + int(v.lo[at])
        assert got == (0 if want is None else int(
            want.scaleb(4, decimal.Context(prec=40))))


# -- `Batch.from_arrow` returns what it returned ----------------------------

def _device_value(dt, v):
    """A python value of `to_pylist` as the device holds it."""
    if dt.id == TypeId.DECIMAL:
        return int(v.scaleb(dt.scale))
    if dt.id == TypeId.DATE32:
        return (v - datetime.date(1970, 1, 1)).days
    if dt.id == TypeId.TIMESTAMP_US:
        return (v - datetime.datetime(1970, 1, 1)) // \
            datetime.timedelta(microseconds=1)
    return v


def _expected_batch_leaves(arr, dt, cap):
    """The padded arrays from `to_pylist` and `to_numpy`, nothing of the
    conversion under test."""
    values = arr.to_pylist()
    n = len(values)
    validity = np.zeros(cap, bool)
    validity[:n] = [v is not None for v in values]
    if dt.is_stringlike:
        raw = [b"" if v is None else v.encode() if isinstance(v, str) else v
               for v in values]
        w = bucket_width(max([len(b) for b in raw] + [1]))
        data = np.zeros((cap, w), np.uint8)
        lengths = np.zeros(cap, np.int32)
        for i, b in enumerate(raw):
            data[i, :len(b)] = np.frombuffer(b, np.uint8)
            lengths[i] = len(b)
        return {"data": data, "lengths": lengths, "validity": validity}
    data = np.zeros(cap, dt.numpy_dtype())
    if dt.id == TypeId.FLOAT64:
        # the doubles' own bits: NaN and -0.0 survive no python detour
        data[:n] = np.where(validity[:n], arr.to_numpy(zero_copy_only=False),
                            0.0)
    else:
        data[:n] = [0 if v is None else _device_value(dt, v) for v in values]
    return {"data": data, "validity": validity}


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("name", sorted(COLUMNS))
def test_batch_from_arrow_returns_what_it_returned(name, shape):
    with conf.scoped(COLUMNS[name][2]):
        table = _table(name, shape)
        batch = Batch.from_arrow(table, schema=_schema(table))
        [col] = batch.columns
        dt = batch.schema[0].dtype
        arr = table.column("v").combine_chunks()
        if pa.types.is_dictionary(arr.type):
            arr = arr.dictionary_decode()
        cap = bucket_capacity(table.num_rows)
        assert (batch.num_rows, batch.capacity) == (table.num_rows, cap)
        want = _expected_batch_leaves(arr, dt, cap)
        if name == "double-bits":
            want["bits"] = want["data"].view(np.uint64)
        got = _leaves(col)
        assert sorted(got) == sorted(want)
        for leaf in want:
            assert isinstance(got[leaf], jax.Array), leaf
            _assert_same_bytes(np.asarray(got[leaf]), want[leaf])
