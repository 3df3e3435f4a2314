"""What the kinds share: the model built from a configuration file, the
seeded weights, the count of compilations, the device's facts."""

import dataclasses
import time


class NoChip(RuntimeError):
    """JAX finds no TPU, or not the number of chips the cell asks for."""


def peaks_for(peaks, device_kind):
    if device_kind not in peaks:
        raise RuntimeError(
            f"chipbench/peaks.json has no row for device kind "
            f"{device_kind!r}: add one with its source, do not borrow another")
    return peaks[device_kind]


def llama_config(config, **kw):
    """The program's ``LlamaConfig`` for a configuration file's dict:
    each field named in ``maps_to`` takes the file's value, bf16."""
    import jax.numpy as jnp

    from sparkdl_tpu.models import LlamaConfig

    fields = {field: config[key] for field, key in config["maps_to"].items()}
    return LlamaConfig(**{"dtype": jnp.bfloat16, **fields, **kw})


def init_params(cfg, seed, keep_f32=lambda path: False):
    """Seeded random weights, drawn and cast to bf16 inside ONE jitted
    program on the device, so that the float32 tree never sits there
    whole (the ``chip_smoke._init_params`` pattern, copied); leaves
    `keep_f32` names (the LoRA adapters) stay float32."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models import Llama

    # shapes do not depend on how attention is computed: draw them
    # through the plain path, not through a kernel at sequence length 8
    model = Llama(dataclasses.replace(cfg, attention="reference",
                                      remat=False))

    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if keep_f32(jax.tree_util.keystr(path))
            else x.astype(jnp.bfloat16), params)

    return jax.jit(init)(jax.random.PRNGKey(seed % 2**32))


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, by this
    process: JAX reports each as one ``backend_compile_duration``
    event. ``since(t)`` counts those that ended after `t`
    (``time.perf_counter``): inside a measured window there are none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.ended = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.ended.append(time.perf_counter())

    def since(self, t):
        return sum(1 for e in self.ended if e >= t)


def cache_everything():
    """Keep every compiled program in the persistent cache, however
    quick its compile, so that only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(jax, chips):
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        raise NoChip(
            f"the cell needs {chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform!r} device(s)")


def device_facts(jax):
    """The device as JAX reports it, with the peak bytes in use on the
    fullest local chip."""
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}
