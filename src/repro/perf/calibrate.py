"""Micro-probe calibration: measure the constants the cost model multiplies.

One calibration is a handful of fitted (slope, intercept) lines, probed ONCE
per (backend, device-count) and persisted to JSON:

  chacha[impl]   us per ChaCha20 block + us per launch, fitted over several
                 wire widths as the secure-minus-plaintext difference of a
                 REAL fused driver round (a standalone kernel call can't
                 see that a round's encrypt and decrypt launches share one
                 CSE'd keystream derivation), plus the secure probe
                 program's compile seconds and jaxpr equation count
                 (the compile-time predictor's scaling anchor);
  all_to_all     us per wire byte + us per collective, through a shard_map
                 `lax.all_to_all` on this process's actual mesh;
  dispatch       us per jitted host->device round trip (trivial program);
  round          us per mapped item + us of fixed per-round machinery,
                 fitted over input sizes through a minimal PLAINTEXT
                 iterative-driver round (bucket_pack + all_to_all + reduce
                 — the real scan body, so the intercept prices the real
                 scan/shard_map overhead), plus its compile stats;
  compile        seconds per jaxpr equation + base, from two plain XLA
                 programs of different sizes (the floor for programs with
                 no keystream in them).

Activation is EXPLICIT: `$REPRO_CALIBRATION=<path>` (or
`repro.perf.model.set_active_model`). Nothing is read implicitly from the
working directory, so with the variable unset every `auto` resolver keeps
its historical default bit-for-bit.

CLI:  PYTHONPATH=src python -m repro.perf.calibrate --out calibration.json
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import asdict, dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

CALIBRATION_ENV = "REPRO_CALIBRATION"
SCHEMA = 1

# payload widths (f32 words per item) for the chacha fit: wire block counts
# span ~10x so both the slope and the intercept are anchored
_CHACHA_WIDTHS = (1, 8, 32)
_CHACHA_WIDTHS_QUICK = (1, 16)
_A2A_WORDS = (1 << 10, 1 << 14)
_ROUND_SIZES = (256, 1024, 4096)


@dataclass(frozen=True)
class Calibration:
    """Fitted probe constants for one (backend, device-count) pair.

    All times are microseconds unless the field name says seconds. `extra`
    carries optional deployment-measured overrides the model consults but
    never probes itself (e.g. "capacity_factor" for a measured key skew).
    """

    backend: str
    n_devices: int
    chacha: dict  # impl -> {us_per_block, launch_us, compile_s, compile_eqns}
    all_to_all: dict  # {us_per_byte, base_us}
    dispatch: dict  # {base_us}
    round: dict  # {us_per_item, base_us, compile_s, compile_eqns}
    compile: dict  # {s_per_eqn, base_s}
    schema: int = SCHEMA
    extra: dict = field(default_factory=dict)

    @property
    def key(self) -> str:
        return f"{self.backend}/{self.n_devices}"

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Calibration":
        return cls(**{k: d[k] for k in cls.__dataclass_fields__ if k in d})


# -- probe plumbing ----------------------------------------------------------


def _time_us(fn, *args, reps: int = 7) -> float:
    """Best steady-state wall time of `fn(*args)` in us (post-warmup).

    Min over reps, the microbenchmark standard: every source of jitter on
    a shared box (scheduler, thermal, GC) only ever ADDS time, so the
    minimum is the least-contaminated estimate of the program's cost —
    and the quantity the bench's interleaved measurement reproduces.
    """
    return _interleaved_best_us([(fn, args)], reps=reps)[0]


def _interleaved_best_us(entries, reps: int = 7) -> list:
    """Best wall time (us) per (fn, args) entry, trials INTERLEAVED.

    A probe that fits a line across program sizes must time every size
    under the SAME machine conditions — compiling the next size's program
    between timing phases (tens of seconds for the secure probes) lets
    load drift corrupt the slope. All entries are warmed first, then
    trials round-robin across them.
    """
    for fn, args in entries:
        jax.block_until_ready(fn(*args))
    best = [float("inf")] * len(entries)
    for _ in range(max(1, reps)):
        for i, (fn, args) in enumerate(entries):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best[i] = min(best[i], (time.perf_counter() - t0) * 1e6)
    return best


def _compile_s(jitted, *args) -> float:
    """Seconds to XLA-compile `jitted(*args)` (lowering excluded)."""
    lowered = jitted.lower(*args)
    t0 = time.perf_counter()
    lowered.compile()
    return time.perf_counter() - t0


def _fit_line(xs, ys) -> tuple[float, float]:
    """Least-squares y = slope*x + intercept, both clamped >= 0."""
    xs = np.asarray(xs, np.float64)
    ys = np.asarray(ys, np.float64)
    if len(xs) < 2 or np.ptp(xs) == 0:
        return 0.0, float(ys.mean())
    slope, intercept = np.polyfit(xs, ys, 1)
    return max(float(slope), 0.0), max(float(intercept), 0.0)


def effective_blocks(rows: int, blocks_per_row: int, impl: str,
                     interpret: bool) -> int:
    """ChaCha block-equivalents a launch actually pays for.

    Mirrors `kernels/chacha20/ops.py::_lane_tile`: interpret mode pads each
    row's block count up to an 8-multiple (min 8) so the emulator runs one
    tile; compiled Pallas pads to full 128-lane VREG multiples; the jnp
    oracle derives exactly the blocks the wire needs. The calibration fit
    and the model's predictor both price THIS quantity, so kernel padding
    is never mistaken for payload work.

    Interpret mode additionally multiplies by `rows`: the emulator's grid
    loop rewrites the FULL (rows, words) buffer once per grid row
    (dynamic_update_slice of the whole output), so its measured cost grows
    as rows^2 x padded blocks — one fitted slope then lands within ~15% on
    1-row and 8-row launches alike, where a linear-in-blocks fit is off by
    ~8x on whichever regime it wasn't anchored to.
    """
    if blocks_per_row == 0 or rows == 0:
        return 0
    if impl == "jnp":
        return rows * blocks_per_row
    if interpret:
        return rows * rows * max(8, -(-blocks_per_row // 8) * 8)
    return rows * max(128, -(-blocks_per_row // 128) * 128)


# -- probes ------------------------------------------------------------------


def _probe_chacha(impl: str, mesh, axis_name: str, widths) -> dict:
    """Crypto cost measured through the REAL secure driver round.

    Times the minimal driver round (`_probe_round`'s spec, payload widened
    to `d` f32 words per item) secure vs PLAINTEXT at each width; the
    difference is exactly what the keystream path adds to one fused round.
    A standalone `chacha20_xor_rows` microbenchmark cannot measure this:
    its per-call host dispatch lands in the intercept, and — decisive on
    the secure path — the fused round's encrypt and decrypt launches
    derive the SAME keystream by construction (that is what decryption
    means for a stream cipher), so XLA CSEs the derivation and a real
    round pays for it once. The fitted intercept is split per launch so
    `predict_round_us`'s launches x launch_us term scales to per-leaf
    wires; the per-dispatch overhead cancels in the secure-minus-plain
    difference.
    """
    from repro.core.driver import IterativeSpec, make_iterative_runner
    from repro.core.shuffle import (
        SecureShuffleConfig,
        record_wire_bytes,
        resolve_chacha_impl,
    )
    from repro.crypto import chacha as chacha_mod
    from repro.tools.jaxprs import total_eqns

    kern_impl, interpret = resolve_chacha_impl(impl)
    r_sh = mesh.shape[axis_name]
    n = -(-256 // r_sh) * r_sh
    n_rounds = 4
    sec = SecureShuffleConfig(
        key_words=chacha_mod.key_to_words(bytes(range(32))),
        nonce_words=chacha_mod.nonce_to_words(b"\x07" * 12),
        impl=impl)

    def map_fn(state, inputs, r):
        keys = jnp.arange(inputs["x"].shape[0], dtype=jnp.int32) % 8
        return keys, {"x": inputs["x"]}

    def reduce_fn(state, keys, values, valid, r):
        s = jnp.sum(jnp.where(valid[:, None], values["x"], 0.0))
        return {"s": state["s"] + lax.psum(s, axis_name)}, {"s": s}

    spec = IterativeSpec(map_fn=map_fn, reduce_fn=reduce_fn, n_rounds=n_rounds)
    # a dedicated tiny-wire anchor leads the sweep: workloads that shuffle
    # AGGREGATES (k-means moves k cluster sums, not n points) ride ~6-block
    # wires, and the crypto cost curve is concave near zero — a fit whose
    # nearest anchor is ~40 blocks extrapolates a badly inflated intercept
    # down into that regime
    anchors = [(-(-16 // r_sh) * r_sh, widths[0])] + [(n, d) for d in widths]
    xs, entries = [], []
    compile_s = compile_eqns = None
    for n_d, d in anchors:
        inputs = {"x": jnp.ones((n_d, d), jnp.float32)}
        state = {"s": jnp.float32(0)}
        secure_runner = make_iterative_runner(spec, mesh, axis_name, secure=sec)
        plain_runner = make_iterative_runner(spec, mesh, axis_name)
        with record_wire_bytes() as recs:
            jaxpr = jax.make_jaxpr(secure_runner.abstract_fn)(
                inputs, state, jnp.uint32(0))
        (rec,) = [r for r in recs if r["secure"] and not r["halted"]]
        launches = max(1, rec["keystream_launches"])
        bpr = max(1, rec["keystream_blocks"] // (launches * r_sh))
        xs.append(launches * effective_blocks(r_sh, bpr, kern_impl, interpret))
        entries.append((secure_runner, (inputs, state)))
        entries.append((plain_runner, (inputs, state)))
        if compile_s is None:
            compile_s = _compile_s(secure_runner.jitted, inputs, state,
                                   jnp.uint32(0))
            compile_eqns = total_eqns(jaxpr)
    timed = _interleaved_best_us(entries)
    ys = [max(0.0, (timed[2 * i] - timed[2 * i + 1]) / n_rounds)
          for i in range(len(anchors))]
    slope, intercept = _fit_line(xs, ys)
    return {"us_per_block": slope, "launch_us": intercept / 2.0,
            "compile_s": float(compile_s), "compile_eqns": int(compile_eqns),
            "resolved": [kern_impl, bool(interpret)]}


def _probe_all_to_all(mesh, axis_name: str, sizes) -> dict:
    r = mesh.shape[axis_name]

    def body(x):
        return lax.all_to_all(x, axis_name, 0, 0, tiled=True)

    xs, ys = [], []
    for words in sizes:
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
            check_vma=False))
        x = jnp.zeros((r * r, max(1, words // r)), jnp.uint32)
        xs.append(x.size // r * 4)  # bytes leaving ONE device's shard
        ys.append(_time_us(fn, x))
    slope, intercept = _fit_line(xs, ys)
    return {"us_per_byte": slope, "base_us": intercept}


def _probe_dispatch() -> dict:
    fn = jax.jit(lambda x: x + 1)
    return {"base_us": _time_us(fn, jnp.zeros((8,), jnp.float32))}


def _probe_round(mesh, axis_name: str, sizes) -> dict:
    """A minimal PLAINTEXT driver round: the real scan/shuffle machinery.

    The intercept prices everything a round pays regardless of payload
    (shard_map + scan step + bucket_pack bookkeeping + the collective's
    base cost at its calibrated size); the slope prices per-mapped-item
    work. Workload map/reduce math rides on the slope — generic, so a
    heavy map_fn is the model's known blind spot (documented there).
    """
    from repro.core.driver import IterativeSpec, make_iterative_runner
    from repro.tools.jaxprs import total_eqns

    n_rounds = 4

    def map_fn(state, inputs, r):
        x = inputs["x"]
        keys = jnp.arange(x.shape[0], dtype=jnp.int32) % 8
        return keys, {"x": x}

    def reduce_fn(state, keys, values, valid, r):
        s = jnp.sum(jnp.where(valid, values["x"], 0.0))
        return {"s": state["s"] + lax.psum(s, axis_name)}, {"s": s}

    spec = IterativeSpec(map_fn=map_fn, reduce_fn=reduce_fn, n_rounds=n_rounds)
    r_sh = mesh.shape[axis_name]
    xs, entries = [], []
    compile_s = compile_eqns = None
    for n in sizes:
        n = -(-n // r_sh) * r_sh
        runner = make_iterative_runner(spec, mesh, axis_name)
        inputs = {"x": jnp.ones((n,), jnp.float32)}
        state = {"s": jnp.float32(0)}
        xs.append(n // r_sh)  # per-shard mapped items, what round_delay sees
        entries.append((runner, (inputs, state)))
        if compile_s is None:
            compile_s = _compile_s(runner.jitted, inputs, state, jnp.uint32(0))
            compile_eqns = total_eqns(
                jax.make_jaxpr(runner.abstract_fn)(inputs, state, jnp.uint32(0)))
    ys = [us / n_rounds for us in _interleaved_best_us(entries)]
    slope, intercept = _fit_line(xs, ys)
    return {"us_per_item": slope, "base_us": intercept,
            "compile_s": float(compile_s), "compile_eqns": int(compile_eqns)}


def _probe_compile() -> dict:
    from repro.tools.jaxprs import total_eqns

    def chain(n):
        def f(x):
            for i in range(n):
                x = jnp.sin(x) + np.float32(i)
            return x
        return f

    xs, ys = [], []
    for n in (16, 160):
        f = chain(n)
        x = jnp.ones((128,), jnp.float32)
        xs.append(total_eqns(jax.make_jaxpr(f)(x)))
        ys.append(_compile_s(jax.jit(f), x))
    slope, intercept = _fit_line(xs, ys)
    return {"s_per_eqn": slope, "base_s": intercept}


# -- entry points ------------------------------------------------------------


def run_calibration(mesh=None, *, axis_name: str = "data",
                    impls=("pallas", "jnp"), quick: bool = False) -> Calibration:
    """Run every probe on this process's backend; return the Calibration.

    `mesh` defaults to a 1-axis mesh over every local device (the shape the
    collective probe and the device-count key describe). `quick` trims the
    fit widths — the CI autotune lane's mode.
    """
    from repro.compat import make_mesh

    if mesh is None:
        n_dev = jax.device_count()
        mesh = make_mesh((n_dev,), (axis_name,))
    widths = _CHACHA_WIDTHS_QUICK if quick else _CHACHA_WIDTHS
    round_sizes = _ROUND_SIZES
    return Calibration(
        backend=jax.default_backend(),
        n_devices=jax.device_count(),
        chacha={impl: _probe_chacha(impl, mesh, axis_name, widths)
                for impl in impls},
        all_to_all=_probe_all_to_all(mesh, axis_name, _A2A_WORDS),
        dispatch=_probe_dispatch(),
        round=_probe_round(mesh, axis_name, round_sizes),
        compile=_probe_compile(),
    )


def save_calibration(cal: Calibration, path: str) -> None:
    """Merge `cal` into the JSON at `path`, keyed by backend/device-count."""
    doc = {"schema": SCHEMA, "calibrations": {}}
    try:
        with open(path) as f:
            loaded = json.load(f)
        if isinstance(loaded.get("calibrations"), dict):
            doc = loaded
    except (OSError, ValueError):
        pass
    doc["calibrations"][cal.key] = cal.to_dict()
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)


def load_calibration(path: str, *, backend: str | None = None,
                     n_devices: int | None = None) -> Calibration | None:
    """Load the entry matching (backend, n_devices); None when absent.

    Defaults to THIS process's backend and device count — a calibration
    probed on a different shape says nothing about this one, so a missing
    key falls back to no model (and therefore to the historical defaults)
    rather than to a wrong one.
    """
    backend = backend if backend is not None else jax.default_backend()
    n_devices = n_devices if n_devices is not None else jax.device_count()
    with open(path) as f:
        doc = json.load(f)
    entry = doc.get("calibrations", {}).get(f"{backend}/{n_devices}")
    return None if entry is None else Calibration.from_dict(entry)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="calibration.json")
    ap.add_argument("--impls", default="pallas,jnp",
                    help="comma-separated chacha impls to probe")
    ap.add_argument("--quick", action="store_true",
                    help="fewer fit points (CI autotune lane)")
    args = ap.parse_args(argv)
    cal = run_calibration(impls=tuple(args.impls.split(",")), quick=args.quick)
    save_calibration(cal, args.out)
    print(f"calibrated {cal.key}: "
          + ", ".join(f"{i}={c['us_per_block']:.3f}us/blk+{c['launch_us']:.0f}us"
                      for i, c in cal.chacha.items())
          + f"; a2a {cal.all_to_all['us_per_byte']*1e3:.3f}ns/B"
          + f"; round {cal.round['base_us']:.0f}us"
          + f" -> {args.out}")


if __name__ == "__main__":
    main()
