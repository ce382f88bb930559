"""Checkpointing with cheap, separately-readable step metadata.

The reference stores one pickled dict ``{epoch, log{...}, optimizer,
model, ema}`` via ``torch.save`` (``train.py:305-317``) — and then its
search driver POLLS those checkpoints every 10 s just to read
``ckpt['epoch']``, deserializing full model weights each time
(``search.py:186-190``).  Here the tensor payload is a msgpack of the
state pytree (flax serialization) and the metadata is a tiny JSON
sidecar, so progress polling never touches tensor bytes
(SURVEY.md section 5, checkpoint/resume).

Writes are atomic (tmp + rename) so a concurrently-polling reader never
sees a torn file — the reference guards this with bare ``except``
retries instead (``search.py:191-192``).

Integrity + rollback (docs/RESILIENCE.md): every save stamps a sha256
content digest and the payload size into the sidecar and rotates a
bounded restore chain (``path``, ``path.prev``, ``path.prev2``, …,
depth ``keep``); :func:`load_checkpoint` verifies the digest and raises
:class:`~fast_autoaugment_tpu.core.resilience.CheckpointCorruptError`
on mismatch, and :func:`load_checkpoint_chain` walks back to the newest
intact snapshot — one torn/corrupt file costs an epoch, not the run.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
from typing import Any, Callable

import jax
import msgpack
import numpy as np
from flax import serialization

from fast_autoaugment_tpu.core.resilience import CheckpointCorruptError
from fast_autoaugment_tpu.utils.logging import get_logger

__all__ = [
    "save_checkpoint",
    "load_checkpoint",
    "load_checkpoint_chain",
    "read_metadata",
    "checkpoint_exists",
    "chain_paths",
    "CheckpointCorruptError",
]

logger = get_logger("faa_tpu.checkpoint")

#: default rollback-chain depth (the live file plus one predecessor)
DEFAULT_KEEP = 2


def _meta_path(path: str) -> str:
    return path + ".meta.json"


def chain_paths(path: str, keep: int = DEFAULT_KEEP) -> list[str]:
    """The restore-chain filenames, newest first: ``path``,
    ``path.prev``, ``path.prev2``, …  (``keep`` total links)."""
    out = [path]
    for i in range(1, max(1, keep)):
        out.append(path + (".prev" if i == 1 else f".prev{i}"))
    return out


def _digest(payload: bytes) -> str:
    return hashlib.sha256(payload).hexdigest()


#: array leaves of at least this many bytes are streamed, not packed
_STREAMED_FROM = 1 << 16


def _stream_payload(state: Any, fh) -> tuple[str, int]:
    """Write ``flax.serialization.to_bytes(state)`` to `fh` without
    building it, and return its ``(sha256 digest, size)``: byte for byte
    the same payload (tests), but a large array leaf goes from the
    device's copy on the host to the file and the digest as it is,
    where ``to_bytes`` copies it four times into one ``bytes`` of the
    whole state — 62 s for a 7.2 GB state, most of a minute of a
    preempted run's exit (PERF.md section 6, PR 35).

    msgpack is written front to back, a container's header and then its
    members, so the map headers and the small leaves come from the
    packer flax uses and a large leaf's ``ext(ndarray, [shape, dtype,
    bin])`` header is spelled out here.  A leaf over flax's chunking
    limit (1 GiB) falls back to ``to_bytes`` of the whole state."""
    digest, size = hashlib.sha256(), 0

    def emit(data) -> None:
        nonlocal size
        fh.write(data)
        digest.update(data)
        size += len(data)

    def walk(node) -> None:
        if isinstance(node, dict):
            emit(msgpack.Packer().pack_map_header(len(node)))
            for key, value in node.items():
                emit(msgpack.packb(key, strict_types=True))
                walk(value)
        elif (isinstance(node, (np.ndarray, jax.Array))
              and _STREAMED_FROM <= node.nbytes <= serialization.MAX_CHUNK_SIZE):
            array = np.ascontiguousarray(node)
            body = array.reshape(-1).view(np.uint8).data
            head = (b"\x93" + msgpack.packb(array.shape)
                    + msgpack.packb(array.dtype.name)
                    + b"\xc6" + struct.pack(">I", len(body)))
            emit(b"\xc9" + struct.pack(">Ib", len(head) + len(body), 1) + head)
            emit(body)
        else:
            emit(serialization.msgpack_serialize(node))

    tree = serialization.to_state_dict(state)
    if any(getattr(leaf, "nbytes", 0) > serialization.MAX_CHUNK_SIZE
           for leaf in jax.tree.leaves(tree)):
        emit(serialization.to_bytes(state))
    else:
        walk(tree)
    return digest.hexdigest(), size


def _rotate_chain(path: str, keep: int) -> None:
    """Shift ``path`` -> ``path.prev`` -> … before a new save lands.

    Each payload/sidecar move is an atomic ``os.replace``; the pair is
    not atomic, but a crash between the two leaves a digest mismatch
    the chain walk detects and skips (docs/RESILIENCE.md, "torn
    rotation").
    """
    links = chain_paths(path, keep)
    # oldest link falls off the end; move back-to-front
    for newer, older in zip(reversed(links[:-1]), reversed(links[1:])):
        for suffix in ("", ".meta.json"):
            src, dst = newer + suffix, older + suffix
            if os.path.exists(src):
                os.replace(src, dst)
            elif os.path.exists(dst):
                # a fresh pair must never sit next to a stale leftover
                os.remove(dst)


def save_checkpoint(path: str, state: Any, metadata: dict | None = None,
                    keep: int = DEFAULT_KEEP):
    """Serialize `state` (any pytree) to `path` atomically; write the
    JSON `metadata` sidecar (stamped with the payload's sha256 digest
    and byte size) after the payload is in place.  ``keep >= 2`` first
    rotates the existing checkpoint into the rollback chain
    (:func:`chain_paths`); ``keep=1`` overwrites in place (the
    pre-chain behavior)."""
    from fast_autoaugment_tpu.utils import faultinject

    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    meta = dict(metadata or {})
    tmp = path + ".tmp"

    fi = faultinject.active_plan()
    if fi is None:
        with open(tmp, "wb") as fh:
            meta["digest"], meta["nbytes"] = _stream_payload(state, fh)
    else:
        # a fault plan tears or corrupts the payload: it needs it whole
        payload = serialization.to_bytes(state)
        meta["digest"] = _digest(payload)
        meta["nbytes"] = len(payload)
        save_n = fi.next_save()
        if fi.torn_at(save_n):
            # simulate a torn non-atomic write: half the payload lands
            # under the FULL payload's digest, then the "process died" —
            # the chain is rotated first, exactly like a real crash
            # mid-save after rotation
            _rotate_chain(path, keep)
            with open(path, "wb") as fh:
                fh.write(payload[: max(1, len(payload) // 2)])
            with open(_meta_path(path), "w") as fh:
                json.dump(meta, fh)
            return
        if fi.corrupt_at(save_n):
            # silent bit-rot: flip bytes AFTER the digest was computed
            corrupted = bytearray(payload)
            corrupted[len(corrupted) // 2] ^= 0xFF
            payload = bytes(corrupted)

        with open(tmp, "wb") as fh:
            fh.write(payload)

    if keep >= 2:
        _rotate_chain(path, keep)
    os.replace(tmp, path)
    tmp_meta = _meta_path(path) + ".tmp"
    with open(tmp_meta, "w") as fh:
        json.dump(meta, fh)
    os.replace(tmp_meta, _meta_path(path))
    # journal evidence (no-op with telemetry off): when/where state hit
    # disk — the trace export renders these as checkpoint markers
    from fast_autoaugment_tpu.core import telemetry

    telemetry.registry().counter(
        "faa_checkpoints_saved_total", "checkpoint chain saves").inc()
    telemetry.emit("checkpoint", os.path.basename(path), action="save",
                   nbytes=meta["nbytes"], epoch=meta.get("epoch"))


def _read_payload(path: str) -> bytes:
    from fast_autoaugment_tpu.utils import faultinject

    fi = faultinject.active_plan()
    if fi is not None and fi.io_error_now():
        raise OSError(f"injected I/O error reading {path}")
    with open(path, "rb") as fh:
        return fh.read()


def _verify_payload(path: str, payload: bytes) -> None:
    """Check the payload against its sidecar's digest/size stamps.

    Pre-chain checkpoints (no ``digest`` key) pass unverified — their
    sidecars never carried one.  A missing sidecar also passes: callers
    that require it gate on :func:`checkpoint_exists` first.
    """
    meta = read_metadata(path)
    if meta is None:
        return
    nbytes = meta.get("nbytes")
    if nbytes is not None and int(nbytes) != len(payload):
        raise CheckpointCorruptError(
            f"{path}: payload is {len(payload)} bytes, sidecar says "
            f"{nbytes} (torn write?)")
    digest = meta.get("digest")
    if digest is not None and _digest(payload) != digest:
        raise CheckpointCorruptError(
            f"{path}: payload sha256 {_digest(payload)[:12]}… does not "
            f"match sidecar digest {str(digest)[:12]}…")


def load_checkpoint(path: str, target: Any, lenient: bool = False,
                    verify: bool = True) -> Any:
    """Restore a pytree of the same structure as `target` from `path`.

    `lenient` merges only the fields present in the file onto the
    template (used for checkpoints imported from the reference's torch
    format, which carry params/batch_stats/ema but no optimizer state —
    the analog of the reference's raw-state-dict handling,
    ``train.py:191-204``).

    `verify` (default) checks the payload against the sidecar's sha256
    digest and size and raises :class:`CheckpointCorruptError` on
    mismatch; pre-digest checkpoints pass through unchecked.
    """
    payload = _read_payload(path)
    if verify:
        try:
            _verify_payload(path, payload)
        except CheckpointCorruptError:
            from fast_autoaugment_tpu.core import telemetry

            telemetry.registry().counter(
                "faa_checkpoints_corrupt_total",
                "checkpoint loads failing digest/size verification").inc()
            telemetry.emit("checkpoint", os.path.basename(path),
                           action="corrupt")
            raise
    from fast_autoaugment_tpu.core import telemetry

    telemetry.registry().counter(
        "faa_checkpoints_loaded_total", "checkpoint restores").inc()
    telemetry.emit("checkpoint", os.path.basename(path), action="load",
                   nbytes=len(payload))
    if not lenient:
        return serialization.from_bytes(target, payload)

    raw = serialization.msgpack_restore(payload)
    template = serialization.to_state_dict(target)

    def merge(tmpl, new):
        if tmpl is None:
            # template structure governs: a field the live state doesn't
            # carry (e.g. ema when conf ema=0) is dropped, not grafted
            return None
        if not isinstance(tmpl, dict) or not isinstance(new, dict):
            return new if new is not None else tmpl
        out = dict(tmpl)
        for k, v in new.items():
            if k in out:
                out[k] = merge(out[k], v)
        return out

    return serialization.from_state_dict(target, merge(template, raw))


def load_checkpoint_chain(
    path: str,
    target: Any,
    *,
    lenient: bool = False,
    keep: int = DEFAULT_KEEP,
    accept: Callable[[dict], bool] | None = None,
) -> tuple[Any, dict, str] | None:
    """Restore from the NEWEST intact link of `path`'s rollback chain.

    Walks ``path``, ``path.prev``, … skipping links that are missing,
    corrupt (digest/size mismatch), unreadable, or rejected by the
    `accept` predicate on their metadata — each skip is logged loudly
    with the reason, so an operator can see exactly what a recovery
    cost.  Returns ``(state, metadata, used_path)`` or ``None`` when no
    link survives.
    """
    for link in chain_paths(path, keep):
        if not checkpoint_exists(link):
            continue
        meta = read_metadata(link) or {}
        if accept is not None and not accept(meta):
            logger.warning(
                "restore chain: skipping %s (metadata rejected: epoch=%s"
                "%s)", link, meta.get("epoch"),
                ", mid-epoch snapshot" if "in_epoch" in meta else "")
            continue
        try:
            state = load_checkpoint(link, target, lenient=lenient)
        except CheckpointCorruptError as e:
            logger.warning("restore chain: skipping CORRUPT link %s (%s)",
                           link, e)
            continue
        except OSError as e:
            logger.warning("restore chain: skipping unreadable link %s (%s)",
                           link, e)
            continue
        if link != path:
            logger.warning(
                "restore chain: recovered from OLDER link %s (epoch %s) — "
                "newer link(s) were corrupt or rejected",
                link, meta.get("epoch"))
        return state, meta, link
    return None


def read_metadata(path: str) -> dict | None:
    """Read the metadata sidecar without touching tensor bytes.

    Returns None if the checkpoint (or sidecar) does not exist yet, or
    if the sidecar is unreadable/torn — callers poll this during search
    phase 1 and must never crash on a file mid-write by another
    process.
    """
    from fast_autoaugment_tpu.utils import faultinject

    fi = faultinject.active_plan()
    if fi is not None and fi.io_error_now():
        return None
    try:
        with open(_meta_path(path)) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        # OSError covers FileNotFoundError plus the transient read
        # failures (EIO, stale NFS handles) the docstring promises to
        # absorb; a torn sidecar surfaces as JSONDecodeError
        return None


def checkpoint_exists(path: str) -> bool:
    """True when `path` holds a plausibly-restorable checkpoint: a
    NONZERO payload plus a parseable metadata sidecar.  A zero-byte
    payload left by a crashed pre-atomic-write process (or a payload
    whose sidecar never landed) does not count."""
    try:
        if os.path.getsize(path) == 0:
            return False
    except OSError:
        return False
    return read_metadata(path) is not None
