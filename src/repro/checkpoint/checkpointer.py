"""Fault-tolerant checkpointing with elastic-reshard restore.

Design (multi-host-aware, CPU-testable):
  * atomic: write to ``step_<N>.tmp/``, fsync, rename to ``step_<N>/`` and
    update ``MANIFEST.json`` last — a crash mid-write never corrupts the
    latest checkpoint; restore always reads the manifest.
  * content: params / optimizer state / data-pipeline step / RNG key, stored
    as raw ``.npy`` per leaf + a msgpack-free JSON tree spec (no pickle).
  * sharded save: a leaf that lives sharded on a mesh (e.g. packed int
    codes row-sharded over 'model' while the LoRDS B/A factors replicate)
    is written as one ``.npy`` *per distinct shard* — no host-side
    all-gather — and the step's ``spec.json`` manifest records each leaf's
    global shape, the shard index windows, and the ``PartitionSpec`` it was
    saved under.  Each host writes only the shards it owns
    (``process_index`` prefix); in this single-process container that
    degenerates to one writer, but the layout and addressing logic are the
    multi-host ones.
  * elastic restore: checkpoints store *logical* shapes; ``restore`` accepts
    any target sharding (a different mesh / chip count) and lets
    jax.device_put reshard — scale-up/scale-down restarts.  Restoring a
    sharded save without target shardings reassembles full arrays.
  * retention: keep the newest ``keep`` checkpoints, delete older ones.
"""
from __future__ import annotations

import json
import os
import shutil

import jax
import numpy as np

from repro.core.quantize import PACK_LAYOUT
from repro.distributed.fault_tolerance import retry_on_transient
from repro.robustness import NO_FAULTS, InjectedFault

__all__ = ["Checkpointer"]

_SEP = "__"


def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}{_SEP}"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}{_SEP}"))
        if hasattr(tree, "_fields"):  # NamedTuple marker
            out[f"{prefix}{_SEP}namedtuple"] = type(tree).__name__
    elif tree is None:
        out[prefix.rstrip(_SEP) + f"{_SEP}none"] = True
    else:
        out[prefix.rstrip(_SEP)] = tree
    return out


def _shard_entries(leaf):
    """Distinct (index-window, host array) pairs for a sharded jax.Array.

    Shards replicated across mesh axes repeat the same index window on
    several devices — only the first copy is written.  Windows come back as
    ``[[start, stop], ...]`` per dim (JSON-friendly).
    """
    seen, out = set(), []
    shape = leaf.shape
    for sh in leaf.addressable_shards:
        idx = tuple(
            (0 if s.start is None else int(s.start),
             dim if s.stop is None else int(s.stop))
            for s, dim in zip(sh.index, shape))
        if idx in seen:
            continue
        seen.add(idx)
        out.append(([list(w) for w in idx], np.asarray(sh.data)))
    return out


def _np_dtype(name: str) -> np.dtype:
    """np.dtype from its saved string name, including the ml_dtypes extras
    (bfloat16 & friends) numpy itself cannot look up by name."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _is_sharded(leaf) -> bool:
    return (isinstance(leaf, jax.Array)
            and len(leaf.sharding.device_set) > 1
            and not leaf.is_fully_replicated)


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3,
                 io_retries: int = 2, io_backoff: float = 0.05,
                 io_jitter: float = 0.0, faults=NO_FAULTS):
        self.dir = directory
        self.keep = keep
        self.io_retries = io_retries
        self.io_backoff = io_backoff
        # decorrelated-jitter fraction for retry sleeps: many hosts saving
        # shards to one filesystem must not retry in lockstep
        self.io_jitter = io_jitter
        # chaos hook: ``ckpt.save_crash`` is consulted once per leaf write,
        # so tests can kill a save at any point mid-step and assert the
        # previous checkpoint stays restorable (atomicity contract).
        self.faults = faults
        os.makedirs(directory, exist_ok=True)

    def _io(self, fn):
        """Every file write/read goes through bounded retry-with-backoff:
        on networked filesystems (the real deployment target) transient
        ``OSError``s are routine and must not kill a training run holding
        hours of optimizer state.  Permanent failures still raise after
        ``io_retries`` attempts."""
        return retry_on_transient(fn, retries=self.io_retries,
                                  backoff=self.io_backoff,
                                  exceptions=(OSError,),
                                  jitter=self.io_jitter)

    # -- save ---------------------------------------------------------------

    def save(self, step: int, state: dict):
        """state: an arbitrary pytree dict (params/opt/data_step/rng...)."""
        tmp = os.path.join(self.dir, f"step_{step}.tmp")
        final = os.path.join(self.dir, f"step_{step}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)

        leaves, treedef = jax.tree_util.tree_flatten(state)
        proc = jax.process_index()
        entries = []
        for i, leaf in enumerate(leaves):
            if self.faults.fires("ckpt.save_crash"):
                raise InjectedFault(
                    f"killed mid checkpoint save (step {step}, leaf {i})")
            if _is_sharded(leaf):
                files, indices = [], []
                for j, (idx, data) in enumerate(_shard_entries(leaf)):
                    name = f"leaf_{i:05d}_p{proc}_s{j}.npy"
                    self._io(lambda: np.save(os.path.join(tmp, name), data))
                    files.append(name)
                    indices.append(idx)
                entries.append({
                    "files": files,
                    "indices": indices,
                    "shape": list(leaf.shape),
                    "dtype": str(leaf.dtype),
                    "pspec": str(leaf.sharding.spec),
                })
            else:
                name = f"leaf_{i:05d}_p{proc}.npy"
                host = np.asarray(jax.device_get(leaf))
                self._io(lambda: np.save(os.path.join(tmp, name), host))
                entries.append({"files": [name], "indices": None,
                                "dtype": str(host.dtype)})
        spec = {
            "version": 2,
            "treedef": str(treedef),
            "leaves": entries,
            "step": step,
            "num_leaves": len(entries),
            "pack_layout": PACK_LAYOUT,
        }
        def write_spec():
            with open(os.path.join(tmp, "spec.json"), "w") as f:
                json.dump(spec, f)

        self._io(write_spec)
        self._io(lambda: os.replace(tmp, final))  # atomic on POSIX
        self._write_manifest(step)
        self._gc()

    def _write_manifest(self, step: int):
        man = os.path.join(self.dir, "MANIFEST.json")
        tmp = man + ".tmp"
        steps = sorted(set(self.all_steps() + [step]))

        def write_man():
            with open(tmp, "w") as f:
                json.dump({"steps": steps, "latest": max(steps)}, f)
            os.replace(tmp, man)

        self._io(write_man)

    def _gc(self):
        steps = self.all_steps()
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s}"),
                          ignore_errors=True)

    # -- restore --------------------------------------------------------------

    def all_steps(self) -> list[int]:
        out = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and not d.endswith(".tmp"):
                try:
                    out.append(int(d.split("_")[1]))
                except ValueError:
                    pass
        return sorted(out)

    def latest_step(self) -> int | None:
        man = os.path.join(self.dir, "MANIFEST.json")
        live = set(self.all_steps())
        if os.path.exists(man):
            try:
                with open(man) as f:
                    data = json.load(f)
                # the manifest may reference a GC'd step after keep-pruning
                cands = [s for s in data.get("steps", []) if s in live]
            except (ValueError, OSError, AttributeError):
                # torn/corrupt manifest: the step dirs themselves are the
                # source of truth (each was atomically renamed into place)
                cands = sorted(live)
            return max(cands) if cands else None
        steps = sorted(live)
        return steps[-1] if steps else None

    def _load_leaf(self, path: str, entry: dict) -> np.ndarray:
        # np.load round-trips the ml_dtypes extras (bfloat16, ...) as raw
        # void records; the manifest dtype views them back bit-exactly
        want = _np_dtype(entry["dtype"]) if entry.get("dtype") else None
        if entry.get("indices") is None:
            arr = self._io(
                lambda: np.load(os.path.join(path, entry["files"][0])))
            if want is not None and arr.dtype != want:
                arr = arr.view(want)
            return arr
        out = np.empty(tuple(entry["shape"]), dtype=want)
        for name, idx in zip(entry["files"], entry["indices"]):
            window = tuple(slice(a, b) for a, b in idx)
            shard = self._io(lambda: np.load(os.path.join(path, name)))
            out[window] = shard.view(want) if shard.dtype != want else shard
        return out

    def restore(self, example_state: dict, step: int | None = None,
                shardings=None) -> dict | None:
        """Restore into the structure of ``example_state``.

        ``shardings``: optional matching tree of jax.sharding.Sharding — the
        elastic-reshard path (device_put onto a *different* mesh than the one
        that saved, or straight back onto the saving layout for bit-exact
        sharded resume).  Returns None when no checkpoint exists.
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        path = os.path.join(self.dir, f"step_{step}")
        with open(os.path.join(path, "spec.json")) as f:
            spec = json.load(f)
        leaves, treedef = jax.tree_util.tree_flatten(example_state)
        if len(leaves) != spec["num_leaves"]:
            raise ValueError(
                f"checkpoint has {spec['num_leaves']} leaves; target structure "
                f"has {len(leaves)} — incompatible state")
        if spec.get("version", 1) >= 2:
            loaded = [self._load_leaf(path, e) for e in spec["leaves"]]
        else:  # v1 layout: one whole-array file per leaf
            loaded = [np.load(os.path.join(path, n)) for n in spec["names"]]
        # uint8 leaves are packed codes: their bytes only mean something in
        # the layout they were packed in
        if (spec.get("pack_layout") != PACK_LAYOUT
                and any(np.dtype(l.dtype) == np.uint8 for l in loaded)):
            raise ValueError(
                f"checkpoint step {step} holds packed codes in pack layout "
                f"{spec.get('pack_layout')!r}; this build reads "
                f"{PACK_LAYOUT!r} — re-quantize instead of restoring")
        if shardings is not None:
            shard_leaves = jax.tree_util.tree_flatten(shardings)[0]
            loaded = [jax.device_put(l, s)
                      for l, s in zip(loaded, shard_leaves)]
        restored = jax.tree_util.tree_unflatten(treedef, loaded)
        return restored

    def saved_pspecs(self, step: int | None = None) -> list | None:
        """The PartitionSpec strings recorded at save time (one per leaf;
        None for unsharded leaves) — the manifest trail that lets operators
        audit how a checkpoint was laid out without loading it."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        path = os.path.join(self.dir, f"step_{step}")
        with open(os.path.join(path, "spec.json")) as f:
            spec = json.load(f)
        if spec.get("version", 1) < 2:
            return [None] * spec["num_leaves"]
        return [e.get("pspec") for e in spec["leaves"]]
