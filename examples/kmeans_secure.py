"""The paper's evaluation workload: secure k-means, convergence + overheads.

Reproduces the §V methodology at CPU scale: convergence under the diag/1000
threshold (Figs. 5-6), the 4-combo encryption x enclave overhead sweep
(Fig. 9), and the paging cliff (Fig. 8) via the SecurePager.

Run:  PYTHONPATH=src python examples/kmeans_secure.py
"""

import numpy as np

import jax

from repro.compat import make_mesh
from repro.compile_cache import enable_compile_cache
from repro.core.kmeans import generate_points, kmeans_fit
from repro.core.paging import SecurePager
from repro.core.shuffle import SecureShuffleConfig
from repro.crypto import chacha
from repro.runtime.jobs import make_cluster, run_kmeans
from repro.runtime.node import SecurityPolicy
from repro.runtime.sim import TimingModel


def main():
    enable_compile_cache()
    mesh = make_mesh((1,), ("data",))
    pts, true_centers = generate_points(20000, 10, seed=0, spread=0.05)

    print("=== convergence (paper Figs. 5-6) ===")
    secure = SecureShuffleConfig(
        key_words=chacha.key_to_words(bytes(range(32))),
        nonce_words=chacha.nonce_to_words(b"\x02" * 12),
    )
    res = kmeans_fit(pts, 10, mesh, secure=secure, init="farthest")
    print(f"diag/1000 threshold: converged in {res.n_iter} iterations "
          f"({res.n_dispatches} fused host dispatches via the convergence-aware "
          f"driver; {res.n_rounds_dispatched} rounds dispatched, halt_fn masked "
          f"{res.n_rounds_dispatched - res.n_iter} post-convergence rounds on device), "
          f"final shift {res.center_shift[-1]:.2e}, inertia {res.inertia:.1f}")
    d = np.linalg.norm(np.asarray(res.centers)[:, None] - true_centers[None], axis=-1)
    print(f"max distance to a true center: {d.min(axis=0).max():.4f}")

    print("\n=== encryption x enclave overheads (paper Fig. 9) ===")
    times = {}
    for encl in (False, True):
        for enc in (False, True):
            cluster, client, _ = make_cluster(
                6, policy=SecurityPolicy(encryption=enc, enclave=encl),
                timing=TimingModel(epc_budget_bytes=32 << 20),
            )
            _, hist = run_kmeans(cluster, client, pts[:400], 5, n_mappers=4,
                                 n_reducers=2, max_iter=2, threshold=0.0)
            times[(encl, enc)] = np.mean([h["elapsed"] for h in hist])
    enc_ovh = 0.5 * ((times[(0, 1)] / times[(0, 0)] - 1) + (times[(1, 1)] / times[(1, 0)] - 1))
    encl_ovh = 0.5 * ((times[(1, 0)] / times[(0, 0)] - 1) + (times[(1, 1)] / times[(0, 1)] - 1))
    print(f"encryption overhead: {enc_ovh*100:.1f}%   (paper: ~5%)")
    print(f"enclave overhead:    {encl_ovh*100:.1f}%  (paper: ~30% inside EPC)")

    print("\n=== paging cliff (paper Fig. 8) ===")
    for ws_pages in (16, 64, 512):
        pager = SecurePager(budget_bytes=256 * 1024, key=b"\x07" * 32)
        for i in range(ws_pages):
            pager.store(f"p{i}", b"\0" * 4096)
        for i in range(ws_pages):
            pager.load(f"p{i}")
        print(f"working set {ws_pages*4096//1024:5d} KiB vs 256 KiB budget: "
              f"{pager.stats.bytes_encrypted + pager.stats.bytes_decrypted:9d} bytes paged")


if __name__ == "__main__":
    main()
