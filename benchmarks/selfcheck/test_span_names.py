"""Every per-layer metric read from a program span names a span the program
still has: the name occurs as a string literal in `auron_tpu/`, so a rename
there fails this test and not a ledger line."""

import glob
import json
import os
import re

import pytest

from benchmarks.harness import cells

SPECS = {os.path.basename(p)[:-len(".json")]: json.load(open(p))
         for p in sorted(glob.glob(os.path.join(
             cells.BENCH_DIR, "layer_metrics", "*.json")))}
SPAN_METRICS = sorted(n for n, s in SPECS.items() if s["source"] == "span")


def program_source() -> str:
    chunks = []
    for root, _dirs, files in os.walk(os.path.join(cells.REPO_DIR,
                                                   "auron_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    chunks.append(f.read())
    return "\n".join(chunks)


SOURCE = program_source()


def test_there_are_span_metrics():
    assert len(SPAN_METRICS) >= 10


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_is_a_literal_of_the_program(metric):
    span = SPECS[metric]["span"]
    assert re.search(r"""["']%s["']""" % re.escape(span), SOURCE), \
        f"{metric} reads span {span!r}, which auron_tpu/ no longer names"


def test_no_program_span_is_named_like_the_window_mark():
    # `execute` is the harness's own annotation (run.py EXECUTE_MARK): a
    # program span of that name would be read as a window
    assert not re.search(r"""span\(\s*["']execute["']""", SOURCE)
