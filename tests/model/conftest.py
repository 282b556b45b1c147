"""What the per-configuration model tests share beside
``alpa_tpu.testing`` (``highest``, ``init_params``, ``jitted``,
``shake``): the toy cell's context and the catalog's rows.  A
configuration's file holds its configuration (``toy_config``, ``toy``),
its reference (``reference``, ``wanted``) and its assertions, and none of
this (``tests/util/test_repo_lint.py`` holds the seam)."""
import json
import os

import pytest

from chipbench import observe, run, traffic

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture
def toy_context(tmp_path):
    """``context(cell, mix, seconds, trace, steady=None)``: the
    ``run.Context`` a benchmark driver runs the toy cell ``cell``
    (``"toy-longcat.agent"``: the configuration's file before the dot)
    under the traffic ``mix`` with.  ``trace=2`` adds a captured second
    and a profile written and parsed to the window, for the test that
    reads the per-layer metrics; a control, which reads ``correct`` and
    the checks, passes 0.  ``steady`` is
    ``conftest.checks_the_same_requests``, where the file takes it."""
    def context(cell, mix, seconds, trace, steady=None):
        config = cell.split(".")[0]
        ctx = run.Context(
            cell={"name": cell, "config": config, "traffic": mix,
                  "chips": 1},
            config=run.load_json(run.HERE, "configs", config + ".json"),
            mix=traffic.load_mix(mix), seed=2147483659, seconds=seconds,
            trace=trace, rehearsal=True, spans=observe.Spans(),
            compile_events=observe.CompileEvents(),
            trace_dir=str(tmp_path / "trace"))
        return steady(ctx) if steady else ctx
    return context


@pytest.fixture
def catalog_row():
    """``row(name)``: the catalog of architectures' row of that name;
    skips where the catalog is not on the machine."""
    def row(name):
        if not os.path.exists(CATALOG):
            pytest.skip("the catalog of architectures is not on this machine")
        with open(CATALOG) as f:
            return next(row for row in map(json.loads, f)
                        if row["name"] == name)
    return row
