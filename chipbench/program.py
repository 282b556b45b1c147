"""The few calls into the system under test that both drivers share: a
configuration file turned into the program's ``GPTConfig``, a seed turned
into a jax key, and the profiler switched on and off."""
import shutil
import time


def key_from_seed(seed: int):
    """A jax key from any whole number up to 2**62: jax's own PRNGKey takes
    32 bits where 64-bit mode is off, and the driver's seeds are larger."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


def gpt_config(config: dict, **overrides):
    """The program's model configuration from a configuration file (keys as
    in a Hugging Face ``config.json``)."""
    import jax.numpy as jnp
    from alpa_tpu.model.gpt_model import GPTConfig
    hidden, ffn = config["hidden_size"], config["ffn_dim"]
    if ffn % hidden:
        raise ValueError("GPTModel takes the MLP width as a whole multiple "
                         f"of the hidden size; got {ffn} / {hidden}")
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=hidden,
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        seq_len=config["max_position_embeddings"], mlp_ratio=ffn // hidden,
        dtype=jnp.dtype(config["dtype"]),
        activation=config["activation_function"],
        pos_offset=config["pos_offset"],
        layer_norm_eps=config["layer_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"], **overrides)


def reference_settings(config: dict) -> dict:
    """What the plain reference needs to know of a configuration."""
    return {"num_heads": config["num_attention_heads"],
            "activation": config["activation_function"],
            "layer_norm_eps": config["layer_norm_eps"],
            "pos_offset": config["pos_offset"]}


class DeviceTrace:
    """The program's capture control (``alpa_tpu.telemetry.trace``
    ``start_capture`` / ``stop_capture``: its spans and jax's profiler, on
    and off together) around a part of a run.  ``start`` and ``stop`` are
    called by a driver; ``summary`` reduces the trace with
    ``chipbench.xplane`` afterwards and deletes it."""

    def __init__(self, ctx):
        from alpa_tpu.telemetry import trace
        self.ttrace = trace
        self.ctx = ctx
        self.dir = ctx.trace_dir
        self.capture = None
        # the traced interval: on time.perf_counter, and the same instants
        # on the clock of the program's recorder
        self.interval = self.interval_us = None
        self._before = []           # the recorder's spans start() cleared
        self._window = None

    def warm_up(self):
        """Start and stop the profiler once and throw the trace away: the
        first start costs seconds, which then fall into no number."""
        self.ttrace.start_capture(self.dir)
        self.ttrace.stop_capture()
        self.ttrace.get_recorder().clear()
        shutil.rmtree(self.dir, ignore_errors=True)

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        self._before = self.ttrace.get_recorder().spans()
        self.ttrace.start_capture(self.dir)
        self.ctx.spans.annotate = True
        self._window = self.ctx.spans.span("traced_window")
        self._window.__enter__()
        self._tic = (time.perf_counter(), self.ttrace.now_us())

    def stop(self):
        toc = (time.perf_counter(), self.ttrace.now_us())
        self.interval = (self._tic[0], toc[0])
        self.interval_us = (self._tic[1], toc[1])
        self._window.__exit__(None, None, None)
        self.ctx.spans.annotate = False
        self.capture = self.ttrace.stop_capture()

    def program_spans(self) -> list:
        """The program's spans of the whole run: what the recorder held
        when the capture began, and all it has held since."""
        return self._before + self.ttrace.get_recorder().spans()

    def summary(self):
        from chipbench import xplane
        if self.capture is None:
            return None
        try:
            shift = self.capture.offset_us()
            program = [(s["name"], (s["ts_us"] + shift) * 1e3,
                        (s["ts_us"] + s["dur_us"] + shift) * 1e3)
                       for s in self.capture.spans
                       if s["name"] != self.ttrace.CAPTURE_MARKER]
            return xplane.reduce_trace(self.dir, program)
        except ValueError:
            if self.ctx.rehearsal:   # a CPU trace has no TPU plane
                return None
            raise
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
