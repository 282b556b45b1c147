"""The few calls into the system under test that both drivers share: a
configuration file turned into the program's ``GPTConfig``, a seed turned
into a jax key, and the profiler switched on and off."""
import shutil


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
    """jax's profiler around a part of the measured window.  ``start`` and
    ``stop`` are called by a driver; ``summary`` reduces the trace with
    ``chipbench.xplane`` after the window."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.dir = ctx.trace_dir
        self.done = False
        self._window = None

    def start(self):
        import jax.profiler
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no python call stacks
        options.host_tracer_level = 2       # the benchmark's annotations
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.ctx.spans.annotate = True
        self._window = self.ctx.spans.span("traced_window")
        self._window.__enter__()

    def stop(self):
        import jax.profiler
        self._window.__exit__(None, None, None)
        self.ctx.spans.annotate = False
        jax.profiler.stop_trace()
        self.done = True

    def summary(self):
        from chipbench import xplane
        if not self.done:
            return None
        try:
            return xplane.reduce_trace(self.dir)
        except ValueError:
            if self.ctx.rehearsal:   # a CPU trace has no TPU plane
                return None
            raise
