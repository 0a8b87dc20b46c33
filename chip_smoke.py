"""Run the secure job service once on a TPU and check every answer.

    python chip_smoke.py            # one chip: the main path at real sizes
    python chip_smoke.py --chips 4  # four chips: the sharded secure sort and
                                    # the secure k-means, each also run on one
                                    # chip and compared

Every job goes through `SecureJobService` with a `SecureShuffleConfig`
session, whose keystream is the compiled Pallas ChaCha20 kernel on the TPU.
Data and keys come from `--seed` alone. Each answer is checked against a
plain numpy reference: sort and grep exactly, k-means centers within
`KMEANS_TOL` of the data's diagonal after the same number of Lloyd rounds
from the same initial centers.

Earlier lines report the device, the resolved implementations, each job's
wall time (cold, which compiles, and warm, on cached programs) and the
runner-cache counters. The last line of standard output is one JSON object
naming the device, printed only when every check passed. Without a TPU the
script exits non-zero before any job runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# job sizes: the paper's k-means shapes (§V: d=2, k=10, diag/1000 rule)
SIZES = {"kmeans": 1 << 24, "sort": 1 << 24, "grep": 1 << 26,
         "kmeans_pallas": 1 << 20}
K, D = 10, 2
GREP_VOCAB = 1 << 16
# three tokens of the vocabulary and one outside it (zero hits)
GREP_PATTERNS = (7, 4242, GREP_VOCAB - 1, GREP_VOCAB + 5)
# largest center error allowed, as a fraction of the data's diagonal: the
# paper's convergence threshold (diag/1000)
KMEANS_TOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def make_data(seed: int, sizes: dict) -> dict:
    """Every input, from the seed alone."""
    from repro.core.kmeans import generate_points

    rng = np.random.default_rng(seed)
    points, _ = generate_points(sizes["kmeans"], K, D, seed=seed)
    return {
        "points": points,
        "values": rng.standard_normal(sizes["sort"], dtype=np.float32),
        "tokens": rng.integers(0, GREP_VOCAB, sizes["grep"], dtype=np.int32),
        "patterns": np.asarray(GREP_PATTERNS, np.int32),
        "key": rng.bytes(32),
    }


def lloyd_reference(points, init, rounds, chunk: int = 1 << 20) -> dict:
    """Plain Lloyd iterations: {r: centers after r rounds}.

    Distances are float32; centers, sums and counts float64. Empty clusters
    keep their center, as the service's reduce does.
    """
    x = np.asarray(points, np.float32)
    c = np.asarray(init, np.float64)
    k = c.shape[0]
    out = {}
    for r in range(1, max(rounds) + 1):
        sums = np.zeros_like(c)
        counts = np.zeros(k)
        c32 = c.astype(np.float32)
        c2 = np.sum(c32 * c32, axis=1)
        for s in range(0, x.shape[0], chunk):
            xs = x[s:s + chunk]
            # nearest center: |x - c|^2 less the |x|^2 every center shares
            a = np.argmin(c2 - 2.0 * (xs @ c32.T), axis=1)
            counts += np.bincount(a, minlength=k)
            for j in range(x.shape[1]):
                sums[:, j] += np.bincount(a, weights=xs[:, j], minlength=k)
        c = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None], c)
        if r in rounds:
            out[r] = c.copy()
    return out


def diagonal(points) -> float:
    return float(np.linalg.norm(points.max(axis=0) - points.min(axis=0)))


def secure_config(key: bytes, session: int):
    """One session per service: a service's round counter starts at 0, so
    two services under one key must not share a nonce."""
    from repro.core.shuffle import SecureShuffleConfig
    from repro.crypto import chacha

    return SecureShuffleConfig(
        key_words=chacha.key_to_words(key),
        nonce_words=chacha.nonce_to_words(session.to_bytes(12, "little")))


def submit(service, kind: str, *args, **kwargs):
    return getattr(service, f"submit_{kind}")(*args, **kwargs)


def timed_job(log, name: str, service, kind: str, *args, **kwargs):
    """Submit a job twice: cold (compiles its programs), then warm.

    Returns the cold result; the warm one must agree with it exactly.
    """
    cold = submit(service, kind, *args, **kwargs)
    res = cold.result()
    warm = submit(service, kind, *args, **kwargs)
    res_warm = warm.result()
    check(warm.runner_misses == 0, f"{name}: warm job compiled "
          f"{warm.runner_misses} programs")
    for key in res:
        check(np.array_equal(np.asarray(res[key]), np.asarray(res_warm[key])),
              f"{name}: warm result {key!r} differs from the cold one")
    log(f"job={name} n={cold.n} bucket={cold.bucket} "
        f"cold_s={cold.latency_s:.3f} warm_s={warm.latency_s:.3f} "
        f"compile_s={cold.latency_s - warm.latency_s:.3f} "
        f"runner_misses={cold.runner_misses} chunks={cold.chunks}")
    return res, cold


def check_kmeans(log, name, res, points, init, reference=None) -> np.ndarray:
    n_iter = int(res["n_iter"])
    if reference is None or n_iter not in reference:
        reference = lloyd_reference(points, init, {n_iter})
    err = float(np.max(np.abs(res["centers"] - reference[n_iter])))
    diag = diagonal(points)
    log(f"check={name} n_iter={n_iter} halted={bool(res['halted'])} "
        f"max_center_err={err:.3e} diag={diag:.4f} err/diag={err / diag:.3e} "
        f"tol/diag={KMEANS_TOL:.0e}")
    check(np.all(np.isfinite(res["centers"])), f"{name}: non-finite centers")
    check(res["centers"].shape == (K, D), f"{name}: centers {res['centers'].shape}")
    check(err <= KMEANS_TOL * diag, f"{name}: centers off the numpy reference "
          f"by {err / diag:.3e} of the diagonal")
    return res["centers"]


def check_sort(log, name, res, expected):
    log(f"check={name} rounds={res['rounds']} dropped={res['dropped'].tolist()} "
        f"counts={[int(c) for c in res['counts']]}")
    check(np.array_equal(res["sorted"], expected),
          f"{name}: output is not numpy's sort of the input")


def one_chip(devices, data: dict, sizes: dict, log) -> None:
    """The main path on one device: every job kind, each checked."""
    from repro.compat import make_mesh
    from repro.serve.service import RunnerCache, SecureJobService

    mesh = make_mesh((1,), ("data",), devices=devices[:1])
    cache = RunnerCache()
    points = data["points"][: sizes["kmeans"]]
    init = points[:K]
    expected_sort = np.sort(data["values"])

    with SecureJobService(mesh, secure=secure_config(data["key"], 1),
                          cache=cache) as svc:
        res, _ = timed_job(log, "kmeans_secure", svc, "kmeans", points, K)
        check_kmeans(log, "kmeans_secure", res, points, init)
        res, _ = timed_job(log, "sort_secure", svc, "sort", data["values"])
        check_sort(log, "sort_secure", res, expected_sort)
        secure_sorted = res["sorted"]
        res, _ = timed_job(log, "grep_secure", svc, "grep", data["tokens"],
                           data["patterns"])
        counts = np.bincount(data["tokens"], minlength=GREP_VOCAB + 16)
        expected = counts[data["patterns"]]
        log(f"check=grep_secure counts={[int(c) for c in res['counts']]} "
            f"expected={expected.tolist()}")
        check(np.array_equal(res["counts"], expected),
              "grep_secure: counts differ from numpy's")

    with SecureJobService(mesh, cache=cache) as plain:
        res, _ = timed_job(log, "sort_plaintext", plain, "sort", data["values"])
        check_sort(log, "sort_plaintext", res, expected_sort)
        check(np.array_equal(res["sorted"], secure_sorted),
              "the secure and plaintext sorts disagree")

    small = points[: sizes["kmeans_pallas"]]
    with SecureJobService(mesh, secure=secure_config(data["key"], 2),
                          kmeans_impl="pallas", cache=cache) as svc:
        res, _ = timed_job(log, "kmeans_secure_pallas_map", svc, "kmeans",
                           small, K)
        check_kmeans(log, "kmeans_secure_pallas_map", res, small, small[:K])
    log(f"runner_cache {json.dumps(cache.stats(), sort_keys=True)}")


def four_chips(devices, data: dict, sizes: dict, log) -> None:
    """The secure sort (sharded carried state) and the secure k-means on a
    four-device mesh, each against numpy and against one device."""
    from repro.compat import make_mesh
    from repro.serve.service import RunnerCache, SecureJobService

    check(len(devices) >= 4, f"--chips 4 needs four devices, found {len(devices)}")
    mesh4 = make_mesh((4,), ("data",), devices=devices[:4])
    mesh1 = make_mesh((1,), ("data",), devices=devices[:1])
    ids = {d.id for d in np.asarray(mesh4.devices).flat}
    log(f"mesh4 devices={sorted(ids)}")
    check(len(ids) == 4, f"the four-device mesh spans {len(ids)} devices")
    cache = RunnerCache()
    points = data["points"][: sizes["kmeans"]]
    init = points[:K]

    # both meshes' jobs are submitted together: each service compiles on its
    # own scheduler thread
    services = {4: SecureJobService(mesh4, secure=secure_config(data["key"], 4),
                                    cache=cache),
                1: SecureJobService(mesh1, secure=secure_config(data["key"], 1),
                                    cache=cache)}
    try:
        check(services[4].state_mode == "sharded",
              f"the sort's carried table is {services[4].state_mode}, not sharded")
        t0 = time.perf_counter()
        handles = {(n, kind): submit(svc, kind, *args)
                   for n, svc in services.items()
                   for kind, args in (("sort", (data["values"],)),
                                      ("kmeans", (points, K)))}
        results = {key: h.result() for key, h in handles.items()}
        log(f"four_chip_jobs wall_s={time.perf_counter() - t0:.3f}")
    finally:
        for svc in services.values():
            svc.close()

    for (n, kind), h in handles.items():
        log(f"job={kind}_secure_{n}chip latency_s={h.latency_s:.3f} "
            f"runner_misses={h.runner_misses} chunks={h.chunks} "
            f"input_devices={h.input_devices}")
        if n == 4:
            for name, ids in h.input_devices.items():
                check(len(set(ids)) == 4,
                      f"{kind} input {name!r} sits on devices {ids}, not four")

    expected_sort = np.sort(data["values"])
    for n in (4, 1):
        check_sort(log, f"sort_secure_{n}chip", results[(n, "sort")], expected_sort)
    check(np.array_equal(results[(4, "sort")]["sorted"],
                         results[(1, "sort")]["sorted"]),
          "the four-chip and one-chip sorts disagree")

    rounds = {int(results[(n, "kmeans")]["n_iter"]) for n in (4, 1)}
    reference = lloyd_reference(points, init, rounds)
    centers = {n: check_kmeans(log, f"kmeans_secure_{n}chip",
                               results[(n, "kmeans")], points, init, reference)
               for n in (4, 1)}
    err = float(np.max(np.abs(centers[4] - centers[1])))
    log(f"check=kmeans_4chip_vs_1chip max_center_err={err:.3e} "
        f"err/diag={err / diagonal(points):.3e}")
    check(err <= KMEANS_TOL * diagonal(points),
          "the four-chip and one-chip k-means centers disagree")
    log(f"runner_cache {json.dumps(cache.stats(), sort_keys=True)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip sort and k-means")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
              "this script measures nothing off the chip", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.core.shuffle import resolve_chacha_impl

    log = lambda line: print(line, flush=True)  # noqa: E731
    log(f"device platform={devices[0].platform} kind={devices[0].device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    cache_dir = Path(enable_compile_cache())
    cached = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    log(f"compile_cache dir={cache_dir} entries_at_start={cached}")
    impl, interpret = resolve_chacha_impl("auto")
    log(f"keystream impl={impl} interpret={interpret}")
    if impl != "pallas" or interpret:
        print("chip_smoke: the keystream does not run the compiled kernel",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    data = make_data(args.seed, SIZES)
    log(f"data seed={args.seed} sizes={SIZES} make_s={time.perf_counter() - t0:.2f}")
    phase = four_chips if args.chips == 4 else one_chip
    try:
        phase(devices, data, SIZES, log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
