"""One kernel a job, the one the chip runs: with no option set, the stage
programs the suite's CPU devices lower are the programs the chip is given,
and no kernel of the stage path picks its branch by backend."""

import ast
import hashlib
import pathlib

import pytest

from auron_tpu.parallel.mesh import data_mesh
from test_agg_input_compaction import HINT, _lowered as _agg_text
from test_spmd_stage import _lowered_join_text

# sha256 of each plan's lowered text at commit a52851b, the parent of the PR
# that retired the kernel-strategy layer, with the options it deleted forced
# to what `auto` resolved to on a TPU and nothing else touched
# (`auron.sort.multipass.enable` on, `auron.agg.grouping.strategy` sort,
# `auron.spmd.gather.compact` on, `auron.kernel.sort.strategy` argsort,
# `auron.kernel.join.probe.strategy` searchsorted; `jax.default_backend`
# not patched: the float64 capability sites take the CPU's arm on both
# sides).  A change that is meant to move these programs takes new digests
# from the tree before it, the way these were taken.
CHIP_PROGRAM = {
    "agg-input-within-target":
        "3ba4b16e5da7eea4b8bbf58f77410a309a634f6d8ea874a874b5b873713aa4fb",
    "agg-shrink-off":
        "3ba4b16e5da7eea4b8bbf58f77410a309a634f6d8ea874a874b5b873713aa4fb",
    "agg-chooses":
        "71c093f1228f8f5f3f871bd013968afa410bdb7535937db591d656d92d47a57f",
    "agg-chooses-four-devices":
        "f46cab202a7445701470292e02a6e8746557e0c8422f24f893a7e39b83c943a2",
    "join-string":
        "ed13fc3944f8778e208279e97c82dbc3f63b3972594b41de35323a214dfbfc81",
    "join-two-keys":
        "c0e4d9b7ef0dfa9d70d54af70d0b6f58fe9db54a556b710acede69f382c1b3b2",
    "join-int64":
        "8b8593b5ab5b827288b42aaae7be5df7dff916cf3be418b68eafc2329a789009",
    "q07-one-device":
        "73282d97d502c3cfed13a79142c6fb19df795fc56d42166ed6f0d70f520bdd68",
}


def _q07_text(tmp_path):
    """The benchmark's query 7 at its configuration's `rehearse_rows`, as
    the session converts it, on one device."""
    from stage_spy import spied_program
    from auron_tpu.frontend import converters, strategy
    from auron_tpu.frontend.converters import ConvertContext
    from benchmarks.harness import cells, datagen
    from benchmarks.queries import q07
    cell = cells.load_cell("tpcds-sf1.q07")
    cat = datagen.generate(str(tmp_path), q07.SCANS,
                           cell.config["rehearse_rows"],
                           cell.config["data_seed"], 5)
    plan = q07.build_plan(cat, cell.traffic["param_sets"][0])
    ctx = ConvertContext()
    converted = converters.convert_recursively(plan, strategy.apply(plan),
                                               ctx)
    program, inputs = spied_program(converted, ctx, data_mesh(1), {})
    return program.lower(inputs).as_text()


_TEXT = {
    # test_agg_input_compaction's `sums` plan
    "agg-input-within-target": lambda _tmp: _agg_text({}),
    "agg-shrink-off": lambda _tmp: _agg_text(
        {"auron.spmd.agg.capacity.hint": 0}),
    "agg-chooses": lambda _tmp: _agg_text(HINT),
    # the exchange's sort-and-scatter, its all_to_all and the counts of
    # what crossed are in the program only over more than one device
    "agg-chooses-four-devices": lambda _tmp: _agg_text(HINT, n_dev=4),
    # test_spmd_stage's broadcast joins, on one device
    "join-string": lambda _tmp: _lowered_join_text("string", n_dev=1),
    "join-two-keys": lambda _tmp: _lowered_join_text("two-keys", n_dev=1),
    "join-int64": lambda _tmp: _lowered_join_text("int64", n_dev=1),
    "q07-one-device": _q07_text,
}


@pytest.mark.parametrize("case", sorted(CHIP_PROGRAM))
def test_default_program_is_the_chips_program(case, tmp_path):
    text = _TEXT[case](tmp_path)
    assert hashlib.sha256(text.encode()).hexdigest() == CHIP_PROGRAM[case]


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
