"""System driver for configurations of kind ``ecg``: the paper's ECG
classifier served through the program's public entries.

A request is a number of raw two-channel windows (one live window, or a
whole recording cut into windows).  Serving it is what a user's call
does: copy the raw samples to the chip, run the Pallas max-min
pre-processing (``repro.data.preprocess.preprocess(raw,
use_pallas=True)``), run the compiled analog chain (``jax.jit`` of
``api.compile(...).apply``: conv -> fc1 -> fc2 as one megakernel), and
bring the logits back to the host, where the class is read.

The weights, the chip's fixed pattern and the raw windows are the
benchmark's, made from the seed; the program gets them as inputs.
"""
from __future__ import annotations

import functools

import numpy as np

from chipbench import ecg_synth, opcount
from chipbench.traffic import span

# NumPy / JAX streams of one seed
STREAM_WEIGHTS, STREAM_POOL, STREAM_REQUESTS = 1, 2, 3


def layer_shapes(cfg: dict) -> dict:
    return {name: (k, n) for name, (_, k, n) in
            zip(("conv", "fc1", "fc2"), opcount.ecg_layers(cfg))}


def make_weights(cfg: dict, key):
    """Every layer's masters, LSBs, gain and the chip's fixed pattern, in
    one jitted call on the device (the layout the program's analog layers
    take: ``w``, ``w_scale``, ``a_scale``, ``gain``, ``fpn``)."""
    import jax

    shapes = layer_shapes(cfg)
    noise = cfg["noise"]
    rows = cfg["analog"]["chunk_rows"]

    @jax.jit
    def make(key):
        import jax.numpy as jnp

        out = {}
        for i, (name, (k, n)) in enumerate(shapes.items()):
            kw, kg, ko = jax.random.split(jax.random.fold_in(key, i), 3)
            w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
            w_scale = jnp.maximum(jnp.abs(w).max(0, keepdims=True),
                                  1e-8) / 63.0
            code_rms = jnp.sqrt(jnp.mean((w / w_scale) ** 2) + 1e-6)
            partial_rms = np.sqrt(float(rows)) * 9.0 * code_rms
            gain = jnp.minimum(1.0, 127.0 / (3.0 * partial_rms + 1e-6))
            fpn = {"chunk_offset": noise["offset_std"] * jax.random.normal(
                ko, (opcount.chunks(k, rows), n), jnp.float32)}
            if noise["mode"] == "full":
                fpn["gain"] = 1.0 + noise["gain_std"] * jax.random.normal(
                    kg, (k, n), jnp.float32)
            else:
                raise ValueError(f"noise mode {noise['mode']!r}")
            out[name] = {"w": w, "w_scale": w_scale,
                         "a_scale": jnp.asarray(1.0 / 31.0, jnp.float32),
                         "gain": gain, "fpn": fpn}
        return out

    return make(key)


def make_pool(cfg: dict, traffic: dict, rng: np.random.Generator):
    """The seeded pool of raw windows that requests draw from."""
    ds = ecg_synth.ECGDatasetConfig(
        n_train=int(traffic["pool_windows"]),
        seed=int(rng.integers(0, 2**31 - 1)), window=cfg["raw_samples"])
    raw, _ = ecg_synth.make_dataset(ds, split="train")
    return np.ascontiguousarray(raw, np.float32)


class System:
    """The program under test, built for one cell and one seed."""

    def __init__(self, cfg: dict, traffic: dict, seeds, peak: dict):
        import jax

        from repro import api
        from repro.core.analog import AnalogConfig
        from repro.core.noise import NoiseConfig
        from repro.data.preprocess import preprocess
        from repro.models import ecg as ECG

        self.cfg, self.traffic, self.peak = cfg, traffic, peak
        self.weights = make_weights(cfg, seeds.key(STREAM_WEIGHTS))
        self.pool = make_pool(cfg, traffic, seeds.rng(STREAM_POOL))
        self.rng = seeds.rng(STREAM_REQUESTS)
        a = cfg["analog"]
        ecfg = ECG.ECGConfig(
            in_channels=cfg["in_channels"], in_len=cfg["in_len"],
            conv_taps=cfg["conv_taps"], conv_stride=cfg["conv_stride"],
            conv_channels=cfg["conv_channels"], hidden=cfg["hidden"],
            classes=cfg["classes"], class_copies=cfg["class_copies"],
            noise=NoiseConfig(mode=cfg["noise"]["mode"],
                              gain_std=cfg["noise"]["gain_std"],
                              offset_std=cfg["noise"]["offset_std"]))
        acfg = AnalogConfig(mode=a["mode"], use_pallas=a["use_pallas"],
                            chunk_rows=a["chunk_rows"])
        spec = ECG.ecg_module_spec(ecfg, epilogue=a["epilogue"])
        self.model = api.compile(spec, self.weights, acfg)
        self.apply = jax.jit(self.model.apply)
        self.preprocess = functools.partial(
            preprocess, window=cfg["pool"], quant_shift=cfg["quant_shift"],
            use_pallas=a["use_pallas"])
        self.window_ops = opcount.ecg_window_ops(cfg)
        self.answers = []            # (pool indices, logits) per request
        self.kernels = {"maxmin_pool": opcount.Work(),
                        "megakernel": opcount.Work()}
        self.step = opcount.Work()   # every window's counted operations
        self.annotate = False
        # recordings are built before the window; a request picks one
        self.recordings = []
        sizes = traffic["request"]["windows"]
        if not isinstance(sizes, int):
            raise ValueError("the ECG driver takes a fixed window count")
        if sizes > 1:
            for _ in range(int(traffic.get("recordings", 1))):
                idx = self.rng.integers(0, len(self.pool), sizes)
                self.recordings.append((idx, self.pool[idx]))
        self._next_recording = 0

    # ---------------------------------------------------------- traffic
    def request(self, sizes: dict):
        n = sizes["windows"]
        if n == 1:
            i = int(self.rng.integers(0, len(self.pool)))
            return np.asarray([i]), self.pool[i:i + 1]
        idx, raw = self.recordings[self._next_recording]
        self._next_recording = (self._next_recording + 1) % len(
            self.recordings)
        if len(idx) != n:
            raise ValueError(f"recordings hold {len(idx)} windows, not {n}")
        return idx, raw

    def call(self, requests) -> int:
        """Serve each request in turn (one client's call is one request):
        raw samples to the chip, pre-processing, the analog chain, logits
        back on the host, the class read there."""
        import jax

        done = 0
        for idx, raw in requests:
            with span("ecg.copy_in", self.annotate):
                x = jax.device_put(raw)
            with span("ecg.device", self.annotate):
                logits = np.asarray(self.apply(self.preprocess(x)))
            with span("ecg.reply", self.annotate):
                logits.argmax(-1)
            self.answers.append((idx, logits))
            self._count(len(idx))
            done += len(idx)
        return done

    def _count(self, windows: int) -> None:
        cfg, peak = self.cfg, self.peak
        self.kernels["maxmin_pool"].add(
            opcount.maxmin_ops(windows, cfg["in_channels"],
                               cfg["raw_samples"], cfg["pool"]),
            opcount.maxmin_bytes(windows, cfg["in_channels"],
                                 cfg["raw_samples"], cfg["pool"]), peak)
        ops = float(windows * self.window_ops)
        self.kernels["megakernel"].add(
            ops, opcount.ecg_chain_bytes(cfg, windows), peak)
        self.step.ops += ops
        self.step.calls += 1

    def reset_counts(self) -> None:
        self.answers.clear()
        self.kernels = {k: opcount.Work() for k in self.kernels}
        self.step = opcount.Work()

    # ------------------------------------------------------------ set-up
    def warm(self) -> None:
        """Run one request of the cell's size: the only shapes the window
        uses."""
        self.call([self.request({"windows": self.traffic["request"]
                                 ["windows"]})])
        self.reset_counts()

    def free_program(self) -> None:
        """Drop the program's state before the reference runs."""
        self.model = self.apply = None


def build(cfg: dict, traffic: dict, seeds, peak: dict) -> System:
    system = System(cfg, traffic, seeds, peak)
    system.warm()
    return system
