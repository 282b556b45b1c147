"""The CPU rehearsal of the four-chip training cell
(``chipbench/configs/toy-gpt-pipeshard.json`` through
``chipbench/drivers/train.py``, two stages on two 1x2 submeshes of four
virtual devices): no cross-mesh edge of a step hands a device array to the
host (ISSUE 46), and the step computes what the direct path computes."""
import jax
import pytest

import alpa_tpu
from alpa_tpu.global_env import global_config
# importing the planner registers its counter families
from alpa_tpu.pipeline_parallel import cross_mesh_resharding  # noqa: F401
from alpa_tpu.pipeline_parallel import runtime_emitter
from alpa_tpu.telemetry import metrics as tmetrics
from chipbench import observe, run, traffic

TOY = run.load_json(run.HERE, "configs", "toy-gpt-pipeshard.json")


@pytest.fixture
def four_devices(monkeypatch):
    init = alpa_tpu.init
    monkeypatch.setattr(
        alpa_tpu, "init",
        lambda cluster="local": init(cluster, devices=jax.devices()[:4]))


@pytest.fixture
def fetched_in_replay(monkeypatch):
    """Shapes of the arrays read to the host (``ArrayImpl._value``) between
    the call of ``pipeshard.replay``'s program and its return, on any
    thread: the transfer pool's run inside it."""
    from jax._src import array as jarray
    fetched, replaying = [], [False]
    inner = jarray.ArrayImpl._value.fget

    def counted(self):
        if replaying[0]:
            fetched.append(self.shape)
        return inner(self)

    execute = runtime_emitter.RegisterFileProgram.execute

    def watched(self, *args, **kwargs):
        replaying[0] = True
        try:
            return execute(self, *args, **kwargs)
        finally:
            replaying[0] = False

    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(counted))
    monkeypatch.setattr(runtime_emitter.RegisterFileProgram, "execute",
                        watched)
    return fetched


def _two_steps(tmp_path, strategy):
    """The warm-up step and one step of the window: their losses (the
    second has the first's gradients in it), and the program's text."""
    prev = global_config.reshard_strategy
    global_config.reshard_strategy = strategy
    try:
        ctx = run.Context(
            cell={"name": "toy-gpt-pipeshard.train",
                  "config": "toy-gpt-pipeshard", "traffic": "toy-lm",
                  "chips": 4},
            config=TOY, mix=traffic.load_mix("toy-lm"), seed=2147483659,
            seconds=0.0, trace=0, rehearsal=True, spans=observe.Spans(),
            compile_events=observe.CompileEvents(),
            trace_dir=str(tmp_path / "trace"))
        obs = run.load_module("drivers", "train").run(ctx)
    finally:
        global_config.reshard_strategy = prev
        alpa_tpu.shutdown()
    assert obs["attempted"] == 1 and obs["failed"] == 0
    checks = obs["checks"]
    assert checks["matches_reference"] and checks["all_finite"], checks
    return checks["first_loss"], checks["last_losses"]


def test_a_step_fetches_nothing_and_matches_the_direct_path(
        tmp_path, four_devices, fetched_in_replay):
    planned = tmetrics.get_registry().get("alpa_reshard_strategy_total")
    fell_back = tmetrics.get_registry().get(
        "alpa_reshard_runtime_fallback_total")
    before = (planned.labels("aligned_relayout").value, fell_back.value)
    losses = _two_steps(tmp_path, "auto")
    # both directions were planned (the activation 0->1, its gradient
    # 1->0), every call found the layout its plan assumed, and nothing
    # came to the host inside the replay of either step
    assert planned.labels("aligned_relayout").value >= before[0] + 2
    assert fell_back.value == before[1]
    assert fetched_in_replay == []
    # the parent's path: the same edges through ``jax.device_put``, which
    # fetches (this says when jax stops doing so), to the same bits
    direct = _two_steps(tmp_path, "direct_p2p")
    assert fetched_in_replay != []
    assert losses == direct


def test_a_traced_toy_step_has_no_account_on_the_cpu(tmp_path,
                                                     four_devices,
                                                     monkeypatch):
    """``--trace 2`` of the toy cell: the capture holds the steps' spans
    and no device event (a CPU trace has no TPU plane), so the account by
    mesh is empty, the six readers of it find nothing, and the report is
    read on the host's clock and says so (ISSUE 49)."""
    from alpa_tpu.telemetry import trace as ttrace
    train = run.load_module("drivers", "train")
    held = []
    describe = train._describe
    monkeypatch.setattr(train, "_describe", lambda ctx, executable: (
        held.append(executable), describe(ctx, executable)))
    ctx = run.Context(
        cell={"name": "toy-gpt-pipeshard.train",
              "config": "toy-gpt-pipeshard", "traffic": "toy-lm",
              "chips": 4},
        config=TOY, mix=traffic.load_mix("toy-lm"), seed=2147483659,
        seconds=0.0, trace=2, rehearsal=True, spans=observe.Spans(),
        compile_events=observe.CompileEvents(),
        trace_dir=str(tmp_path / "trace"))
    try:
        obs = train.run(ctx)
        assert obs["failed"] == 0 and obs["checks"]["all_finite"]
        capture = ttrace.last_capture()
        steps = [s for s in capture.spans if s["name"] == "pipeshard.step"]
        assert len(steps) == ctx.mix["trace_steps"]
        # the executable said what a capture needs of it, and the capture
        # kept it: only the device events are missing
        (executable,) = held
        hooks = executable._last_program().hooks
        assert any(kept["program"].hooks is hooks
                   for kept in capture._pipelines)
        assert capture.device_time()["programs"] == {}
        assert capture.pipeline_time() == {}
        for name in ("mesh_idle_max_pct", "pipeline_bubble_pct",
                     "dispatch_starved_pct", "edge_exposed_pct",
                     "step_boundary_idle_pct", "collective_exposed_pct"):
            assert run.metric_reader(name)(obs) is None
        report = executable.get_perf_report()
        assert report.source == "trace" and report.aligned, report.notes
        assert "the host's clock" in report.format_text().splitlines()[0]
        assert all(b.idle_us is None for b in report.bubbles.values())
        # given the capture itself: the same step, the same clock
        assert executable.get_perf_report(capture).source == "trace"
    finally:
        ttrace.set_enabled(False)
        alpa_tpu.shutdown()
