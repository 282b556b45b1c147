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


@pytest.fixture
def selecting_decode():
    """``case(queries, heads, dn, dv, starts, seed)``: one selecting
    layer's DECODE at widths ``ops/latent_attention.py``
    ``absorbed_under_mask`` takes (a latent of 128 and a rotary key of 64
    in rows of 256 channels, a cache of 4,096 positions, 64 selected), in
    float32, the rows' first new positions ``starts``: the arguments of
    ``gpt_model.latent_attention_over_selection`` after its ``scale``, and
    what ``latent_attention_gathered`` makes of the same cache and
    table."""
    import jax
    import jax.numpy as jnp
    from alpa_tpu.model import gpt_model

    def case(queries, heads, dn, dv, starts, seed=0):
        rank, dr, sk, topk = 128, 64, 4096, 64
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 5))

        def rnd(*shape):
            return jax.random.normal(next(keys), shape, jnp.float32)

        b = len(starts)
        index = jnp.asarray(starts, jnp.int32)
        q_pos = index[:, None] + jnp.arange(queries)[None]
        scores = jnp.where(jnp.arange(sk)[None, None] <= q_pos[..., None],
                           rnd(b, queries, sk), -jnp.inf)
        positions, real = gpt_model.selected_positions(scores, topk)
        q_nope, q_pe = rnd(b, queries, heads, dn), rnd(b, queries, heads, dr)
        rows = rnd(b, sk, gpt_model.latent_row_width(rank, dr))
        w_kv_b = rnd(rank, heads, dn + dv) * rank ** -0.5
        scale = (dn + dr) ** -0.5
        want = gpt_model.latent_attention_gathered(
            q_nope, q_pe, rows, w_kv_b, scale, positions, real)
        return (q_nope, q_pe, rows, w_kv_b, scale, index, positions,
                real), want
    return case


@pytest.fixture
def selecting_cores_traced():
    """``traced(queries)``: {core: count} of the gauge
    ``alpa_selecting_decode_core`` over ``selecting_decode``'s cache, for
    a decode of ``queries`` a row."""
    from alpa_tpu.telemetry import metrics as tmetrics

    def traced(queries):
        counts = {}
        # (summed over the series' other label, the heads: another file's
        # decode of as many queries over as long a cache, traced earlier
        # in this process, is a series of its own)
        for key, value in tmetrics.get_registry().snapshot().items():
            if key.startswith("alpa_selecting_decode_core") and \
                    'queries="%d"' % queries in key and \
                    'positions="4096"' in key:
                core = key.split('core="')[1].split('"')[0]
                counts[core] = counts.get(core, 0) + value
        return counts
    return traced
