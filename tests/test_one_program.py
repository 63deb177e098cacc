"""One kernel a job, the one the chip runs: with no option set, the stage
programs the suite's CPU devices lower are the programs the chip is given,
and no kernel of the stage path picks its branch by backend."""

import ast
import hashlib
import pathlib

import pytest

from auron_tpu.parallel.mesh import data_mesh
from test_agg_input_compaction import (
    HINT, RUNGS, _lowered as _agg_text, case_branches,
)
from test_spmd_stage import _lowered_join_text

# sha256 of each plan's lowered text at commit a52851b, the parent of the PR
# that retired the kernel-strategy layer, with the options it deleted forced
# to what `auto` resolved to on a TPU and nothing else touched
# (`auron.sort.multipass.enable` on, `auron.agg.grouping.strategy` sort,
# `auron.spmd.gather.compact` on, `auron.kernel.sort.strategy` argsort,
# `auron.kernel.join.probe.strategy` searchsorted; `jax.default_backend`
# not patched: the float64 capability sites take the CPU's arm on both
# sides).  A change that is meant to move these programs takes new digests
# from the tree before it, the way these were taken.  PR 36 moved the five
# that hold an aggregate, on purpose (a sorted-segment reduction reads its
# segments' bounds from the aggregate's boundaries: 20, 30, 40 and 32 calls
# of `searchsorted` left them); PR 38 moved the three in which an
# aggregate chooses (the choice is a `lax.switch` over a ladder of widths and
# the counter says which each device took; query 7's two aggregates, which
# chose nothing at `rehearse_rows`, now choose between a rung of 8,192 rows
# and their 65,536-row inputs) and added `agg-chooses-rungs`; the two that
# choose nothing and the three join programs are what they were.  The join
# chain moved `q07-one-device` alone: its four joins are a chain over the
# scan of store_sales, so the program counts the rows the first join left and
# runs the three later joins at the narrowest of 1,024 rows, 8,192 rows or
# the scan's 65,536 that holds them (a `lax.switch`; the later joins' build
# halves before it, their probe halves in each side).  It added
# `q01-one-device`, whose text is its parent's: query 1 has no such chain.
CHIP_PROGRAM = {
    "agg-input-within-target":
        "a46c6612703b3eea44126a17fb0994671808bf1b61d0ed5093a8e4e5bf1be517",
    "agg-shrink-off":
        "a46c6612703b3eea44126a17fb0994671808bf1b61d0ed5093a8e4e5bf1be517",
    "agg-chooses":
        "41758e9e2fa27ccc92a6ef39042b48b6df1d89d28462130b79ff95405a44fa52",
    "agg-chooses-four-devices":
        "8c742f669a97288cc6761850d707965769fa36249d2d00a0c3b41bdb17d4e492",
    "agg-chooses-rungs":
        "97ec57c5cab51bacc28f2af64f3130040c6e8a9d6ca3ede2b81eb49caf61beb3",
    "join-string":
        "ed13fc3944f8778e208279e97c82dbc3f63b3972594b41de35323a214dfbfc81",
    "join-two-keys":
        "c0e4d9b7ef0dfa9d70d54af70d0b6f58fe9db54a556b710acede69f382c1b3b2",
    "join-int64":
        "8b8593b5ab5b827288b42aaae7be5df7dff916cf3be418b68eafc2329a789009",
    "q07-one-device":
        "d44b9c1f43037e5c0d50e9072cefe450ddea7f7f95efdc8b4382bbabe55cae6b",
    "q01-one-device":
        "7ba7016d1ab1d43fd962f589c7d1eb094d2e603013fd6b16b6ffd05f7a94a8c6",
}


def _query_text(cell_name, query, tmp_path):
    """A benchmark cell's query at its configuration's `rehearse_rows`, with
    the traffic's first parameter set, as the session converts it, on one
    device."""
    from stage_spy import spied_program
    from auron_tpu.frontend import converters, strategy
    from auron_tpu.frontend.converters import ConvertContext
    from benchmarks.harness import cells, datagen
    cell = cells.load_cell(cell_name)
    cat = datagen.generate(str(tmp_path), query.SCANS,
                           cell.config["rehearse_rows"],
                           cell.config["data_seed"], 5)
    plan = query.build_plan(cat, cell.traffic["param_sets"][0])
    ctx = ConvertContext()
    converted = converters.convert_recursively(plan, strategy.apply(plan),
                                               ctx)
    program, inputs = spied_program(converted, ctx, data_mesh(1), {})
    return program.lower(inputs).as_text()


def _q07_text(tmp_path):
    from benchmarks.queries import q07
    return _query_text("tpcds-sf1.q07", q07, tmp_path)


def _q01_text(tmp_path):
    from benchmarks.queries import q01
    return _query_text("tpcds-sf10.q01", q01, tmp_path)


_TEXT = {
    # test_agg_input_compaction's `sums` plan
    "agg-input-within-target": lambda _tmp: _agg_text({}),
    "agg-shrink-off": lambda _tmp: _agg_text(
        {"auron.spmd.agg.capacity.hint": 0}),
    "agg-chooses": lambda _tmp: _agg_text(HINT),
    # the exchange's sort-and-scatter, its all_to_all and the counts of
    # what crossed are in the program only over more than one device
    "agg-chooses-four-devices": lambda _tmp: _agg_text(HINT, n_dev=4),
    # two rungs under the target, 32 and 256 rows
    "agg-chooses-rungs": lambda _tmp: _agg_text(RUNGS),
    # test_spmd_stage's broadcast joins, on one device
    "join-string": lambda _tmp: _lowered_join_text("string", n_dev=1),
    "join-two-keys": lambda _tmp: _lowered_join_text("two-keys", n_dev=1),
    "join-int64": lambda _tmp: _lowered_join_text("int64", n_dev=1),
    "q07-one-device": _q07_text,
    # query 1's template plan: two joins over scans that feed aggregates,
    # and joins over an aggregate's output — no chain over a source
    "q01-one-device": _q01_text,
}


_LOWERED = {}


def _text(case, tmp_path):
    """Lowered once a case for the tests of this file (one xdist worker
    runs a file's tests)."""
    if case not in _LOWERED:
        _LOWERED[case] = _TEXT[case](tmp_path)
    return _LOWERED[case]


@pytest.mark.parametrize("case", sorted(CHIP_PROGRAM))
def test_default_program_is_the_chips_program(case, tmp_path):
    text = _text(case, tmp_path)
    assert hashlib.sha256(text.encode()).hexdigest() == CHIP_PROGRAM[case]


# the `searchsorted` calls of each program: the search side of a K = 1
# join's lookup holds the one a join keeps — query 7's first join one, and
# each of its three later joins one in each of the chain's three sides
_JOINS = {"join-string": 1, "join-two-keys": 1, "join-int64": 1,
          "q07-one-device": 1 + 3 * 3, "q01-one-device": 5}


@pytest.mark.parametrize("case", sorted(CHIP_PROGRAM))
def test_no_aggregate_searches_for_its_segments(case, tmp_path):
    """A sorted-segment reduction reads its segments' bounds from the
    boundaries the aggregate found (ops/agg/exec.py `_group_segments`):
    the lowered program calls `searchsorted` once a join and never from
    an aggregate (query 7's called it 36 times, 32 of them for the bounds
    of the 16 reductions of `agg#3` and `agg#1`)."""
    import re
    text = _text(case, tmp_path)
    assert len(re.findall(r"call @\S*searchsorted", text)) == \
        _JOINS.get(case, 0)


# the sides of every conditional of each program, in the text's order: a
# K = 1 join over a directly addressable key chooses between two probes,
# an aggregate among the widths under its input and the input's own
_SIDES = {
    "join-int64": [2],
    "agg-chooses": [2],                     # the target or the input
    "agg-chooses-four-devices": [2, 2],
    # 32, 256, 1,024 or the input's 8,192; 32, 256 or the input's 1,024
    "agg-chooses-rungs": [4, 3],
    # the first join's probe; the three later joins' build halves; the
    # chain's 1,024, 8,192 or 65,536 rows, each side holding the later
    # joins' probe halves; `agg#3` and `agg#1`, each the hint's rung of
    # 8,192 rows or its input's 65,536 (its other rung, 65,536, is no
    # narrower)
    "q07-one-device": [2, 2, 2, 2, 3] + [2, 2, 2] * 3 + [2, 2],
    # five probes and six aggregates' choices, in the text's order
    "q01-one-device": [2] * 11,
}


@pytest.mark.parametrize("case", sorted(CHIP_PROGRAM))
def test_every_conditional_has_the_sides_it_chooses_among(case, tmp_path):
    assert case_branches(_text(case, tmp_path)) == _SIDES.get(case, [])


RETIRED_OPTIONS = [
    "auron.kernel.sort.strategy", "auron.kernel.sort.radix.min.rows",
    "auron.kernel.join.probe.strategy",
    "auron.kernel.join.partitioned.min.rows",
    "auron.kernel.join.partitioned.max.rows",
    "auron.kernel.join.bucket.bits", "auron.kernel.group.strategy",
    "auron.kernel.group.onehot.max.segments",
    "auron.kernel.cost.profile.path", "auron.kernel.cost.calibrate",
    "auron.sort.multipass.enable", "auron.agg.grouping.strategy",
    "auron.agg.hash.table.max.bits", "auron.segments.sorted.enable",
    "auron.spmd.gather.compact", "auron.pallas.enable",
    "auron.perf.export.path",
]


@pytest.mark.parametrize("name", RETIRED_OPTIONS)
def test_a_retired_option_is_an_unknown_option(name):
    """There is nothing left to select: reading, setting or scoping one
    raises what any unknown option raises."""
    from auron_tpu.config import conf
    with pytest.raises(KeyError):
        conf.get(name)
    with pytest.raises(KeyError):
        conf.set(name, "on")
    with pytest.raises(KeyError):
        with conf.scoped({name: "on"}):
            pass


# The only code that may ask which backend it runs on: XLA:TPU has no 64-bit
# bitcast and demotes float64, so these are the one path that runs on each
# platform, not alternatives (each says so where it asks).
CAPABILITY_SITES = {
    ("exprs/hashing.py", "f64_bits_u32_pair"),
    ("ops/sort_keys.py", "_orderable_u64_from_f64"),
    ("ops/sort_keys.py", "f64_bits_of_column"),
    # `auron.sort.f64.exactbits` auto: the sidecar where float64 is demoted
    ("ops/sort_keys.py", "f64_exact_bits_enabled"),
}


def _asks_the_backend(fn: ast.AST):
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "default_backend":
            yield node.lineno
        if isinstance(node, ast.Compare) and any(
                isinstance(side, ast.Attribute) and side.attr == "platform"
                for sub in [node.left, *node.comparators]
                for side in ast.walk(sub)):
            yield node.lineno


def test_no_kernel_picks_its_branch_by_backend():
    import auron_tpu
    root = pathlib.Path(auron_tpu.__file__).parent
    found = set()
    for package in ("ops", "parallel", "exprs"):
        for path in sorted((root / package).rglob("*.py")):
            tree = ast.parse(path.read_text())
            inside = set()
            for fn in ast.walk(tree):
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    for line in _asks_the_backend(fn):
                        inside.add(line)
                        found.add((str(path.relative_to(root)), fn.name))
            outside = set(_asks_the_backend(tree)) - inside
            assert not outside, (path, sorted(outside))
    assert found == CAPABILITY_SITES
