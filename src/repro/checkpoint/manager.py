"""Sharded, MAC-verified, atomic checkpoints with elastic restore.

Fault-tolerance contract (designed for 1000+ nodes, exercised in tests):
  * atomic: write to `step_<n>.tmp/`, fsync, rename — a crash mid-save never
    corrupts the latest checkpoint;
  * integrity: every leaf file carries a ChaCha20-keyed polynomial MAC
    (paper's tamper/freshness model applied at rest); a flipped bit fails
    restore loudly;
  * sharded: leaves are saved as independent .npy blobs keyed by pytree path
    (on a real pod each host saves only its addressable shards — the layout
    here is the degenerate 1-host case of that scheme);
  * elastic: restore() takes the *target* shardings, so a checkpoint written
    on one mesh restores onto a different mesh shape (resharding happens via
    device_put against the new NamedShardings);
  * data-cursor: the input pipeline state (keystream counter, rng) rides
    along, so secure-ingest streams resume exactly.
"""

from __future__ import annotations

import json
import os
import shutil
import zlib

import numpy as np

import jax

from repro.crypto.mac import mac_keys_from_keystream, mac_tag_host, mac_verify_host


class CheckpointError(RuntimeError):
    pass


def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], f"{prefix}{k}/"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}/"))
    else:
        out[prefix[:-1]] = tree
    return out


class CheckpointManager:
    def __init__(self, directory: str, key: bytes = b"\x5c" * 32, keep: int = 3):
        self.dir = directory
        self.key = key
        self.keep = keep
        os.makedirs(directory, exist_ok=True)

    def _mac(self, path_label: str, arr: np.ndarray):
        kw = np.frombuffer(self.key, "<u4")
        nw = np.frombuffer(b"ckpt-mac----", "<u4")
        ctr = (zlib.crc32(path_label.encode()) ^ 0x5A5A) & 0x7FFFFFFF  # process-stable
        rs, ss = mac_keys_from_keystream(kw, nw, ctr)
        pad = (-arr.nbytes) % 4
        words = np.frombuffer(arr.tobytes() + b"\x00" * pad, "<u4")
        return rs, ss, words

    def save(self, step: int, tree, extra: dict | None = None):
        """Atomic sharded save of a pytree of arrays."""
        tmp = os.path.join(self.dir, f"step_{step:08d}.tmp")
        final = os.path.join(self.dir, f"step_{step:08d}")
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        flat = _flatten(jax.tree.map(lambda x: np.asarray(x), tree))
        manifest = {"step": step, "leaves": {}, "extra": extra or {}}
        for path, arr in flat.items():
            fname = path.replace("/", "__") + ".npy"
            np.save(os.path.join(tmp, fname), arr)
            rs, ss, words = self._mac(path, arr)
            tag = mac_tag_host(words, rs, ss)
            manifest["leaves"][path] = {
                "file": fname,
                "dtype": str(arr.dtype),
                "shape": list(arr.shape),
                "mac": [int(t) for t in tag],
            }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        shutil.rmtree(final, ignore_errors=True)
        os.rename(tmp, final)
        self._gc()
        return final

    def _gc(self):
        steps = self.list_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"), ignore_errors=True)

    def list_steps(self):
        out = []
        for n in os.listdir(self.dir):
            if n.startswith("step_") and not n.endswith(".tmp"):
                out.append(int(n[5:]))
        return sorted(out)

    def latest_step(self):
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, target_tree, shardings=None):
        """Restore into the STRUCTURE of target_tree; `shardings` (same
        structure, NamedShardings) enables elastic restore onto a new mesh."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        flat_target = _flatten(target_tree)
        flat_shard = _flatten(shardings) if shardings is not None else {}
        loaded = {}
        for path in flat_target:
            meta = manifest["leaves"].get(path)
            if meta is None:
                raise CheckpointError(f"missing leaf {path} in checkpoint {step}")
            arr = np.load(os.path.join(d, meta["file"]))
            rs, ss, words = self._mac(path, arr)
            if not mac_verify_host(words, rs, ss, np.array(meta["mac"], np.uint32)):
                raise CheckpointError(f"MAC mismatch for {path} — tampered/corrupt")
            if list(arr.shape) != list(np.shape(flat_target[path])):
                raise CheckpointError(
                    f"shape mismatch for {path}: ckpt {arr.shape} vs target "
                    f"{np.shape(flat_target[path])}"
                )
            sh = flat_shard.get(path)
            loaded[path] = jax.device_put(arr, sh) if sh is not None else arr

        def rebuild(tree, prefix=""):
            if isinstance(tree, dict):
                return {k: rebuild(tree[k], f"{prefix}{k}/") for k in tree}
            if isinstance(tree, (list, tuple)):
                t = [rebuild(v, f"{prefix}{i}/") for i, v in enumerate(tree)]
                return type(tree)(t)
            return loaded[prefix[:-1]]

        return rebuild(target_tree), manifest["extra"]
