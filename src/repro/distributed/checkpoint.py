"""Checkpoint/restart for long-running distributed jobs.

Two granularities:

* :class:`ChunkStore` — the Gram pipeline's unit of fault tolerance. Every
  completed PairBlock's results land as one CRC-protected, atomically
  renamed file plus a manifest record. Restart = replay manifest, recompute
  only missing blocks. First-writer-wins semantics make straggler
  speculation safe: a duplicate completion of the same block is a no-op.
* :func:`save_array_checkpoint` / :func:`load_array_checkpoint` — pytree
  checkpoints for LM training state (params/optimizer/step), also
  CRC + atomic-rename, with a rolling ``keep_last`` window.

Manifest = append-only journal (DESIGN.md §10.3). The original
read-modify-rewrite of one ``manifest.json`` per completed block was
O(blocks²) in total I/O and, worse, NOT crash-safe: a kill between read
and atomic rewrite could persist a manifest missing entries whose block
files exist. The store now appends one fsync'd JSON line per event to
``manifest.jsonl``:

    {"op": "add",        "block": 17, "crc": ..., "n_pairs": ...}
    {"op": "quarantine", "block": 17, "reason": "crc mismatch ..."}
    {"op": "note",       ...}            # driver health/summary records

Replay folds the journal in order: the FIRST ``add`` for a block wins
(straggler speculation) — unless a later ``quarantine`` retired it, after
which a subsequent ``add`` (the recompute) takes effect again. A torn
final line (crash mid-append) is tolerated and dropped on replay; the
journal is compacted (atomic rewrite of the folded state) when garbage
exceeds a threshold. A legacy ``manifest.json`` found without a journal
is migrated on first open.

No external deps: npz + json. On a real fleet the directory would live on
a parallel filesystem / object store; the protocol (atomic rename +
append-only journal) is the portable part.
"""
from __future__ import annotations

import json
import os
import warnings
import zlib
from typing import Any, Iterable

import numpy as np

import jax

from repro import obs

__all__ = ["ChunkStore", "assemble_blocks", "save_array_checkpoint",
           "load_array_checkpoint"]


def _atomic_write(path: str, data: bytes) -> None:
    """Write-fsync-rename. The tmp suffix is pid PLUS random bytes —
    pid alone collides across hosts on shared storage — and the tmp file
    is unlinked on ANY failure between write and rename (the old code
    stranded it forever; :class:`ChunkStore` additionally reaps strays
    left by a hard kill, which no in-process cleanup can catch)."""
    tmp = path + f".tmp.{os.getpid()}.{os.urandom(4).hex()}"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.rename(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


class ChunkStore:
    """Directory-backed store of per-block results with a journaled
    manifest (module docstring; DESIGN.md §10.3).

    The store assumes a SINGLE live writer per directory (the Gram
    driver; a crashed predecessor is by definition dead), which is what
    makes reaping every ``*.tmp.*`` stray at ``__init__`` safe.
    """

    def __init__(self, root: str, reap_tmps: bool = True,
                 compact_threshold: float = 4.0):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self._journal_path = os.path.join(root, "manifest.jsonl")
        self._legacy_path = os.path.join(root, "manifest.json")
        self._compact_threshold = compact_threshold
        self._cache = None          # (size, folded-state)
        if reap_tmps:
            self.reap_stale_tmps()
        self._migrate_legacy()
        # compact eagerly at open: restart is the one moment no writer
        # is mid-append and the journal is about to be replayed anyway
        st = self._state()
        live = len(st["blocks"]) + len(st["quarantined"]) + len(
            st["notes"])
        if st["n_lines"] > 64 and st["n_lines"] > compact_threshold * \
                max(live, 1):
            self.compact_manifest()

    # -- journal ----------------------------------------------------------
    def reap_stale_tmps(self) -> list[str]:
        """Delete stranded ``*.tmp.*`` files (crash between write and
        rename). Returns the reaped names."""
        reaped = []
        for name in os.listdir(self.root):
            if ".tmp." in name:
                try:
                    os.unlink(os.path.join(self.root, name))
                    reaped.append(name)
                except OSError:
                    pass
        return reaped

    def _migrate_legacy(self) -> None:
        if os.path.exists(self._journal_path) or \
                not os.path.exists(self._legacy_path):
            return
        with open(self._legacy_path) as f:
            legacy = json.load(f)
        lines = [json.dumps({"op": "add", "block": int(k), **v})
                 for k, v in sorted(legacy.items(),
                                    key=lambda kv: int(kv[0]))]
        _atomic_write(self._journal_path,
                      ("\n".join(lines) + "\n").encode()
                      if lines else b"")

    def _fold(self, data: bytes) -> dict:
        """Replay journal bytes into folded state. A torn tail line
        (crash mid-append) parses as garbage and is dropped; any OTHER
        unparseable line is counted (real corruption — the journal is
        append-only, so only the tail can legitimately be torn)."""
        blocks: dict[int, dict] = {}
        quarantined: dict[int, dict] = {}
        notes: list[dict] = []
        raw = data.split(b"\n")
        # every append ends in a newline, so bytes after the last one
        # are a torn append, even when they happen to parse as a record
        tail = raw.pop()
        n_lines = torn = int(bool(tail.strip()))
        for i, line in enumerate(raw):
            if not line.strip():
                continue
            n_lines += 1
            try:
                rec = json.loads(line)
            except ValueError:
                torn += 1
                if i < len(raw) - 1:        # not the (possibly torn) tail
                    warnings.warn(
                        f"manifest journal line {i} unparseable "
                        "(mid-file corruption); skipped")
                continue
            op = rec.get("op", "add")
            if op == "add":
                bid = int(rec["block"])
                if bid not in blocks:       # first writer wins
                    blocks[bid] = {k: v for k, v in rec.items()
                                   if k not in ("op", "block")}
                    quarantined.pop(bid, None)   # recompute cleared it
            elif op == "quarantine":
                bid = int(rec["block"])
                blocks.pop(bid, None)
                quarantined[bid] = {k: v for k, v in rec.items()
                                    if k not in ("op", "block")}
            elif op == "note":
                notes.append({k: v for k, v in rec.items() if k != "op"})
        return {"blocks": blocks, "quarantined": quarantined,
                "notes": notes, "n_lines": n_lines, "n_torn": torn}

    def _state(self) -> dict:
        """Folded journal state, cached by file size (append-only ⇒ any
        concurrent append grows the file, so size is a valid version)."""
        try:
            size = os.path.getsize(self._journal_path)
        except OSError:
            size = -1
        if self._cache is not None and self._cache[0] == size:
            return self._cache[1]
        data = b""
        if size >= 0:
            with open(self._journal_path, "rb") as f:
                data = f.read()
        st = self._fold(data)
        self._cache = (size, st)
        return st

    def _append(self, record: dict) -> None:
        line = (json.dumps(record) + "\n").encode()
        with open(self._journal_path, "ab") as f:
            f.write(line)
            f.flush()
            os.fsync(f.fileno())
        self._cache = None

    def compact_manifest(self) -> int:
        """Atomically rewrite the journal as its folded state (one line
        per live record). Returns the number of lines dropped."""
        st = self._state()
        lines = [json.dumps({"op": "add", "block": bid, **entry})
                 for bid, entry in sorted(st["blocks"].items())]
        lines += [json.dumps({"op": "quarantine", "block": bid, **entry})
                  for bid, entry in sorted(st["quarantined"].items())]
        lines += [json.dumps({"op": "note", **n}) for n in st["notes"]]
        _atomic_write(self._journal_path,
                      ("\n".join(lines) + "\n").encode()
                      if lines else b"")
        self._cache = None
        return st["n_lines"] - len(lines)

    # -- manifest queries -------------------------------------------------
    def done_blocks(self) -> set[int]:
        return set(self._state()["blocks"])

    def block_entry(self, block_id: int) -> dict | None:
        """The manifest record of one completed block (crc, n_pairs,
        plus any driver ``meta`` — health counters, escalation rung)."""
        return self._state()["blocks"].get(int(block_id))

    def quarantined_blocks(self) -> dict[int, dict]:
        """Blocks quarantined (CRC mismatch / torn file) and not yet
        successfully recomputed — never silently part of the Gram."""
        return dict(self._state()["quarantined"])

    def notes(self) -> list[dict]:
        """Free-form journal records (driver health summaries)."""
        return list(self._state()["notes"])

    def note(self, **fields) -> None:
        """Append a free-form record to the journal (driver summaries:
        per-bucket non-convergence counts, quarantined pairs, ladder
        escalations — the 'accounted for in the manifest' channel)."""
        self._append({"op": "note", **fields})

    # -- results ----------------------------------------------------------
    def block_path(self, block_id: int) -> str:
        return os.path.join(self.root, f"block_{block_id:08d}.npz")

    def save_block(self, block_id: int, rows: np.ndarray, cols: np.ndarray,
                   values: np.ndarray, iterations: np.ndarray,
                   meta: dict | None = None,
                   **extra: np.ndarray) -> bool:
        """Returns False if the block was already recorded (speculation).

        ``extra`` arrays (e.g. the gradient Gram blocks ``grad_<theta>``
        of GramDriver.run_with_grad) ride in the same npz under their
        given names and come back verbatim from :meth:`load_block`;
        ``meta`` (JSON-serializable) rides in the manifest record
        (:meth:`block_entry`) — the driver's per-block health channel."""
        with obs.span("mgk.save"):
            if block_id in self.done_blocks():
                return False
            import io
            buf = io.BytesIO()
            np.savez(buf, rows=rows, cols=cols, values=values,
                     iterations=iterations, **extra)
            data = buf.getvalue()
            path = self.block_path(block_id)
            _atomic_write(path, data)
            self._append({"op": "add", "block": int(block_id),
                          "crc": zlib.crc32(data),
                          "n_pairs": int(len(rows)), **(meta or {})})
            return True

    def quarantine_block(self, block_id: int, reason: str) -> None:
        """Retire a block from the done set (journal tombstone) and move
        its file aside for forensics. A later :meth:`save_block` of the
        same id (the recompute) takes effect despite first-writer-wins."""
        path = self.block_path(block_id)
        if os.path.exists(path):
            try:
                os.replace(path, path + ".quarantined")
            except OSError:
                pass
        self._append({"op": "quarantine", "block": int(block_id),
                      "reason": reason})

    def load_block(self, block_id: int,
                   on_error: str = "raise") -> dict[str, np.ndarray] | None:
        """Load one block, verifying its CRC against the manifest.

        The CRC is computed over the WHOLE file, so truncation (a torn
        chunk restored from a crashed copy) is caught identically to bit
        corruption, before np.load ever parses the bytes.

        on_error: "raise" (default) raises IOError on a corrupt/missing/
        truncated chunk; "quarantine" instead journals a tombstone,
        moves the bad file aside, and returns None — the restart path's
        recompute-instead-of-abort mode (DESIGN.md §10.3)."""
        with obs.span("mgk.load"):
            if on_error not in ("raise", "quarantine"):
                raise ValueError(f"unknown on_error={on_error!r}")
            path = self.block_path(block_id)
            entry = self.block_entry(block_id)
            err = None
            data = None
            if entry is None:
                err = f"block {block_id} not in manifest"
            else:
                try:
                    with open(path, "rb") as f:
                        data = f.read()
                except OSError as e:
                    err = f"block {block_id} unreadable: {e}"
            if err is None:
                want, got = entry["crc"], zlib.crc32(data)
                if want != got:
                    kind = "truncated" if len(data) == 0 else "corrupt"
                    err = (f"block {block_id} CRC mismatch ({got} !="
                           f" {want}) — {kind} chunk")
            if err is not None:
                if on_error == "quarantine":
                    self.quarantine_block(block_id, err)
                    return None
                raise IOError(err + "; delete the file (or load with "
                              "on_error='quarantine') to force recompute")
            import io
            return dict(np.load(io.BytesIO(data)))

    def assemble_gram(self, n: int, normalize: bool = False,
                      key: str = "values", strict: bool = True,
                      expected_blocks: Iterable[int] | None = None
                      ) -> np.ndarray:
        """Gather all completed blocks into the (symmetric) Gram matrix
        (``key`` selects which per-block array — e.g. a ``grad_<theta>``
        gradient block). With ``expected_blocks``, missing ids are
        reported by id; either way ``strict=True`` (default) refuses to
        return a Gram with silent NaN holes (:func:`assemble_blocks`)."""
        done = sorted(self.done_blocks())
        if expected_blocks is not None:
            missing = sorted(set(int(b) for b in expected_blocks)
                             - set(done))
            if missing:
                msg = (f"{len(missing)} block(s) missing from store: "
                       f"{missing[:20]}"
                       + ("..." if len(missing) > 20 else ""))
                if strict:
                    raise ValueError(msg)
                warnings.warn(msg)
        K = assemble_blocks((self.load_block(bid) for bid in done), n,
                            key, strict=strict)
        if normalize:
            d = np.sqrt(np.diag(K))
            K = K / d[:, None] / d[None, :]
        return K


def assemble_blocks(blocks, n: int, key: str = "values",
                    strict: bool = True) -> np.ndarray:
    """THE fill-and-mirror Gram assembly convention (NaN init for
    missing entries, symmetric scatter by each block's own rows/cols) —
    single implementation shared by :meth:`ChunkStore.assemble_gram` and
    the driver's in-memory path (distributed/gram.py).

    A NaN hole in the result means a missing block or an excluded
    (quarantined) pair — either way, silently returning it poisons any
    downstream training run. ``strict=True`` (default) raises instead,
    reporting the uncovered index pairs; ``strict=False`` warns and
    returns the holed matrix (callers that want the hole MASK can take
    ``np.isnan`` of it — the quarantine-aware driver path does)."""
    M = np.full((n, n), np.nan, np.float64)
    for blk in blocks:
        if blk is None:
            continue          # a quarantined block (load_block -> None)
        M[blk["rows"], blk["cols"]] = blk[key]
        M[blk["cols"], blk["rows"]] = blk[key]
    holes = np.argwhere(np.isnan(M))
    if holes.size:
        ij = [tuple(int(v) for v in h) for h in holes[:10]]
        msg = (f"Gram assembly left {len(holes)} NaN hole(s) "
               f"(missing blocks or quarantined pairs), e.g. {ij}")
        if strict:
            raise ValueError(
                msg + "; pass strict=False to get the holed matrix")
        warnings.warn(msg)
    return M


# -- pytree checkpoints for LM training --------------------------------------

def _flatten_with_names(tree: Any):
    flat, treedef = jax.tree_util.tree_flatten(tree)
    return flat, treedef


def save_array_checkpoint(root: str, step: int, tree: Any,
                          keep_last: int = 3) -> str:
    os.makedirs(root, exist_ok=True)
    flat, _ = _flatten_with_names(tree)
    arrays = {f"a{i}": np.asarray(x) for i, x in enumerate(flat)}
    import io
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    data = buf.getvalue()
    path = os.path.join(root, f"ckpt_{step:010d}.npz")
    _atomic_write(path, data)
    meta = {"step": step, "crc": zlib.crc32(data), "n_arrays": len(flat)}
    _atomic_write(path + ".json", json.dumps(meta).encode())
    # rolling window
    ckpts = sorted(p for p in os.listdir(root)
                   if p.startswith("ckpt_") and p.endswith(".npz"))
    for old in ckpts[:-keep_last]:
        os.remove(os.path.join(root, old))
        meta_p = os.path.join(root, old + ".json")
        if os.path.exists(meta_p):
            os.remove(meta_p)
    return path


def load_array_checkpoint(root: str, tree_like: Any,
                          step: int | None = None) -> tuple[Any, int]:
    """Restore the latest (or given-step) checkpoint into tree_like's
    structure. Verifies CRC; skips corrupt checkpoints and falls back to
    the previous one (fault tolerance on restore)."""
    ckpts = sorted(p for p in os.listdir(root)
                   if p.startswith("ckpt_") and p.endswith(".npz"))
    if step is not None:
        ckpts = [p for p in ckpts if p == f"ckpt_{step:010d}.npz"]
    if not ckpts:
        raise FileNotFoundError(f"no checkpoints under {root}")
    for name in reversed(ckpts):
        path = os.path.join(root, name)
        try:
            with open(path, "rb") as f:
                data = f.read()
            with open(path + ".json") as f:
                meta = json.load(f)
            if zlib.crc32(data) != meta["crc"]:
                continue  # corrupt; try the previous one
            import io
            loaded = np.load(io.BytesIO(data))
            flat, treedef = jax.tree_util.tree_flatten(tree_like)
            restored = [loaded[f"a{i}"] for i in range(len(flat))]
            return jax.tree_util.tree_unflatten(treedef, restored), \
                meta["step"]
        except (IOError, KeyError):
            continue
    raise IOError(f"all checkpoints under {root} are corrupt")
