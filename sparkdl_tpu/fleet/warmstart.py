"""The persisted AOT warm-start cache: a content-addressed on-disk
store of serialized compiled executables, so a freshly started worker
serves its first request with ZERO compiles on its books.

examples/export_deploy.py measures the gap this closes — cold first
request ~161ms (trace + XLA compile on the request path) vs ~21ms
warmed — but a warmup still pays the compile at process start, once
per process, forever. The Julia-to-TPU AOT work (PAPERS.md, arxiv
1810.09868) names the fix: persist the COMPILED artifact, not the
program. Here the first process to compile a (signature,
params-shape, backend) combination serializes the executable
(``jax.experimental.serialize_executable``); every later process —
the Nth scale-out replica, tomorrow's redeploy — deserializes and
installs it behind ``ModelFunction.jitted()``
(:meth:`~sparkdl_tpu.graph.function.ModelFunction.install_aot`), so
its CompileLog records an ``aot_load`` transfer event and NO compile.
The scale-out drill (tools/ci.sh step 22) gates exactly that:
``compiles_of("<model>.jitted") == 0`` in the fresh process, first
request inside the steady-state band.

The store follows the corpus-snapshot discipline
(sparkdl_tpu/inputsvc/snapshot.py) to the letter:

* **content addressing** — the key is ``blake2b(v<VERSION> |
  signature | params-shape | backend)``: a changed input signature,
  a changed params tree (structure, shapes, dtypes — VALUES
  excluded, so a hot-swap reuses the executable), a different
  backend/device/jax version, or a format bump each land in a
  DIFFERENT key and compile cold. Staleness is unreachable by
  construction.
* **self-validating blob** — the executable payload is framed with
  magic | version | length | blake2b digest. A truncated or
  corrupted blob fails CLOSED: counted
  (``fleet.warmstart_corruptions``), deleted, and the caller
  compiles cold — never a stale or garbage executable.
* **versioned manifest** — ``MANIFEST.json`` pins version / key /
  signature / backend; an unreadable or mismatched manifest wipes
  the entry (``fleet.warmstart_invalidations``) and rebuilds.

Hits/misses/writes count in ``fleet.warmstart_hits`` / ``_misses`` /
``_writes``. The cache root comes from the constructor or
``SPARKDL_TPU_FLEET_CACHE``; without either the cache is disabled
(every call a no-op miss) so the fleet layer needs no disk to run.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import struct
import threading
import time
from typing import Any, Dict, Optional

from sparkdl_tpu.obs import default_registry

logger = logging.getLogger(__name__)

#: cache FORMAT version: part of the key (a bump makes every old
#: entry unreachable-cold) AND pinned in the manifest + blob header
WARMSTART_VERSION = 1

#: blob-file magic
BLOB_MAGIC = b"AOT1"

#: blob header: magic | u16 version | u64 payload_len | blake2b-32
_BLOB_HEADER = struct.Struct(">4sHQ32s")

MANIFEST_NAME = "MANIFEST.json"
BLOB_NAME = "executable.aot"

#: in-process lock for manifest check-then-act (the snapshot-store
#: precedent: concurrent deploys sharing a store must not race the
#: validation into spurious wipes)
_manifest_lock = threading.Lock()


class WarmStartCorruption(Exception):
    """A cache blob failed validation (bad magic/version/digest,
    truncation). Always handled inside :meth:`WarmStartCache.load` —
    the bad blob is deleted and the caller compiles cold; it never
    escapes to a request."""


def signature_key(model_fn, batch_size: int) -> str:
    """The model's COMPILED interface, name-agnostic: input names +
    per-row shapes/dtypes at the serve batch, plus output names —
    replicas and renamed deployments of one program share an entry."""
    sig = sorted(
        (n, tuple(int(d) if d is not None else -1 for d in shape),
         str(dtype))
        for n, (shape, dtype) in model_fn.input_signature.items())
    outs = sorted(model_fn.output_names or [])
    return f"b{int(batch_size)}|{sig!r}|{outs!r}"


def params_shape_key(params) -> str:
    """The params pytree's SHAPE identity: structure + leaf
    shapes/dtypes, values excluded — a weight hot-swap must reuse the
    executable; a layer added/resized must not."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = [(tuple(getattr(v, "shape", ())),
               str(getattr(v, "dtype", type(v).__name__)))
              for v in leaves]
    return f"{treedef!r}|{shapes!r}"


def backend_key() -> str:
    """The executable's ABI: backend, device kind, device count, jax
    version — a serialized executable is only loadable where all four
    match."""
    import jax
    devices = jax.devices()
    kind = getattr(devices[0], "device_kind", "?") if devices else "?"
    return (f"{jax.default_backend()}|{kind}|{len(devices)}"
            f"|jax{jax.__version__}")


def warmstart_key(model_fn, batch_size: int) -> str:
    """The content address: compiled interface x params shape x
    backend ABI x format version → one hex store key."""
    h = hashlib.blake2b(digest_size=16)
    h.update(f"v{WARMSTART_VERSION}"
             f"|{signature_key(model_fn, batch_size)}"
             f"|{params_shape_key(model_fn.params)}"
             f"|{backend_key()}".encode("utf-8"))
    return h.hexdigest()


def _encode_blob(payload: bytes) -> bytes:
    digest = hashlib.blake2b(payload, digest_size=32).digest()
    return _BLOB_HEADER.pack(BLOB_MAGIC, WARMSTART_VERSION,
                             len(payload), digest) + payload


def _read_blob(path: str) -> bytes:
    """Read + validate the framed blob → the pickled executable
    payload. Raises :class:`WarmStartCorruption` on ANY validation
    failure — the fail-closed half of the contract."""
    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) < _BLOB_HEADER.size:
        raise WarmStartCorruption(
            f"warm-start blob {path!r} is truncated below its header")
    magic, version, payload_len, digest = _BLOB_HEADER.unpack(
        raw[:_BLOB_HEADER.size])
    if magic != BLOB_MAGIC:
        raise WarmStartCorruption(
            f"warm-start blob {path!r} has bad magic {magic!r}")
    if version != WARMSTART_VERSION:
        raise WarmStartCorruption(
            f"warm-start blob {path!r} is format v{version}; this "
            f"process reads v{WARMSTART_VERSION}")
    payload = raw[_BLOB_HEADER.size:]
    if len(payload) != payload_len:
        raise WarmStartCorruption(
            f"warm-start blob {path!r} is truncated: header promises "
            f"{payload_len} payload bytes, file holds {len(payload)}")
    if hashlib.blake2b(payload, digest_size=32).digest() != digest:
        raise WarmStartCorruption(
            f"warm-start blob {path!r} failed its digest check "
            "(corrupted on disk)")
    return payload


def _placement_device(model_fn):
    """The one device ``model_fn``'s params live on — a fleet replica
    pinned by the packing plan, else the default device. Placing the
    params here is not extra work: the warmup batch needs them there
    anyway, and the placement is cached."""
    import jax
    for leaf in jax.tree_util.tree_leaves(model_fn.device_params()):
        if isinstance(leaf, jax.Array):
            (device,) = leaf.devices()
            return device
    return jax.local_devices()[0]


class WarmStartCache:
    """The on-disk executable store (module docstring). One instance
    per registry; instances hold only the root path and local tallies,
    so they pickle as-is (the store is shared THROUGH the filesystem,
    not through the object)."""

    def __init__(self, root: Optional[str] = None):
        self.root = root or os.environ.get("SPARKDL_TPU_FLEET_CACHE")
        self.hits = 0
        self.misses = 0
        self.writes = 0
        self.corruptions = 0
        self.invalidations = 0

    @property
    def enabled(self) -> bool:
        return bool(self.root)

    # -- store layout --------------------------------------------------------

    def _dir(self, key: str) -> str:
        assert self.root is not None
        return os.path.join(self.root, key)

    def _manifest(self, model_fn, batch_size: int, key: str) -> dict:
        return {"version": WARMSTART_VERSION, "key": key,
                "signature": signature_key(model_fn, batch_size),
                "params_shape": hashlib.blake2b(
                    params_shape_key(model_fn.params).encode("utf-8"),
                    digest_size=16).hexdigest(),
                "backend": backend_key()}

    def _validate_manifest(self, directory: str, manifest: dict
                           ) -> bool:
        """Validate-or-create (the snapshot ``_ensure_manifest``
        discipline): matching → warm; missing → created (cold);
        unreadable or MISMATCHED → wiped + recreated, counted."""
        manifest_path = os.path.join(directory, MANIFEST_NAME)
        with _manifest_lock:
            existing = None
            if os.path.exists(manifest_path):
                try:
                    # sparkdl-lint: allow[H8] -- the hold is the point: validate-wipe-rewrite must be atomic vs sibling deploys of this process, and a manifest is tens of bytes
                    with open(manifest_path) as f:
                        existing = json.load(f)
                except (OSError, ValueError) as e:
                    logger.warning(
                        "fleet warm-start: manifest %r is unreadable "
                        "(%s); invalidating the entry", manifest_path,
                        e)
            if existing == manifest:
                return True
            if existing is not None or os.path.exists(manifest_path):
                self.invalidations += 1
                default_registry().counter(
                    "fleet.warmstart_invalidations").add()
                for name in os.listdir(directory):
                    try:
                        os.remove(os.path.join(directory, name))
                    except OSError as e:
                        logger.warning(
                            "fleet warm-start: could not remove "
                            "stale %r: %s", name, e)
            tmp = (f"{manifest_path}.tmp.{os.getpid()}"
                   f".{threading.get_ident()}")
            # sparkdl-lint: allow[H8] -- same atomic validate-wipe-rewrite section as the snapshot store: a sibling deploy must not read the entry between the wipe and this rewrite
            with open(tmp, "w") as f:
                json.dump(manifest, f, indent=1, sort_keys=True)
            os.replace(tmp, manifest_path)
            return False

    # -- the warm path -------------------------------------------------------

    def load(self, model_fn, batch_size: int) -> bool:
        """Install the persisted executable behind ``model_fn``'s
        jitted program, if a valid entry exists. True = warm hit (the
        first request will pay zero compile); False = cold (missing,
        disabled, invalidated, or corrupt — corrupt blobs are counted,
        deleted, and the caller compiles normally, never a stale
        read)."""
        if not self.enabled or model_fn.backend != "jax":
            return False
        key = warmstart_key(model_fn, batch_size)
        directory = self._dir(key)
        blob_path = os.path.join(directory, BLOB_NAME)
        if not os.path.exists(blob_path):
            self.misses += 1
            default_registry().counter(
                "fleet.warmstart_misses").add()
            return False
        os.makedirs(directory, exist_ok=True)
        if not self._validate_manifest(
                directory, self._manifest(model_fn, batch_size, key)):
            # the wipe took the blob with it — cold by construction
            self.misses += 1
            default_registry().counter(
                "fleet.warmstart_misses").add()
            return False
        t0 = time.perf_counter()
        try:
            payload = _read_blob(blob_path)
            serialized, in_tree, out_tree = pickle.loads(payload)
            from jax.experimental import serialize_executable
            # pin the load to the one device this replica's weights
            # live on: without execution_devices a one-device
            # executable is loaded across ALL local devices and its
            # first call fails on the shard count
            compiled = serialize_executable.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=[_placement_device(model_fn)])
        # sparkdl-lint: allow[H12] -- broad by design: the blob came off disk and a garbage executable can fail ANYWHERE inside pickle/deserialize; every failure is counted + logged + deleted right here, and the caller compiles cold
        except Exception as e:
            # failed CLOSED: drop the bad blob, compile cold — never
            # a garbage executable on the dispatch path
            self.corruptions += 1
            default_registry().counter(
                "fleet.warmstart_corruptions").add()
            logger.warning(
                "fleet warm-start: entry %s failed validation (%s: "
                "%s); compiling cold", key, type(e).__name__, e)
            try:
                os.remove(blob_path)
            except OSError as rm_err:
                logger.debug("fleet warm-start: removing bad blob "
                             "failed: %s", rm_err)
            self.misses += 1
            default_registry().counter(
                "fleet.warmstart_misses").add()
            return False
        model_fn.install_aot(compiled,
                             wall_s=time.perf_counter() - t0,
                             blob_bytes=len(payload))
        self.hits += 1
        default_registry().counter("fleet.warmstart_hits").add()
        return True

    # -- the write path ------------------------------------------------------

    def save(self, model_fn, batch_size: int) -> bool:
        """AOT-compile ``model_fn`` at the serve batch shape and
        persist the serialized executable (atomic tmp + rename, the
        snapshot publish discipline). Shape-only lowering — no params
        or inputs move to device here. False when disabled or the
        signature has unknown dims."""
        if not self.enabled or model_fn.backend != "jax":
            return False
        sig = model_fn.input_signature
        if any(d is None for shape, _ in sig.values() for d in shape):
            return False
        import jax
        from jax.experimental import serialize_executable
        params_structs = jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(
                tuple(getattr(v, "shape", ())),
                getattr(v, "dtype", None)),
            model_fn.params)
        input_structs = {
            k: jax.ShapeDtypeStruct((int(batch_size),)
                                    + tuple(shape), dtype)
            for k, (shape, dtype) in sig.items()}
        # a lowering or compile error propagates: the installed
        # backends all serialize executables, so nothing here is a
        # capability probe
        compiled = jax.jit(model_fn.apply_fn).lower(
            params_structs, input_structs).compile()
        serialized, in_tree, out_tree = (
            serialize_executable.serialize(compiled))
        key = warmstart_key(model_fn, batch_size)
        directory = self._dir(key)
        os.makedirs(directory, exist_ok=True)
        self._validate_manifest(
            directory, self._manifest(model_fn, batch_size, key))
        payload = pickle.dumps((serialized, in_tree, out_tree))
        blob_path = os.path.join(directory, BLOB_NAME)
        tmp = f"{blob_path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "wb") as f:
            f.write(_encode_blob(payload))
        os.replace(tmp, blob_path)
        self.writes += 1
        default_registry().counter("fleet.warmstart_writes").add()
        return True

    # -- readout -------------------------------------------------------------

    def state(self) -> Dict[str, Any]:
        """ONE shape shared by ``/statusz`` and flight bundles."""
        entries = 0
        if self.enabled and os.path.isdir(self.root):
            entries = sum(
                1 for n in os.listdir(self.root)
                if os.path.exists(os.path.join(self.root, n,
                                               BLOB_NAME)))
        with _manifest_lock:    # where the tally is written
            invalidations = self.invalidations
        return {"enabled": self.enabled, "root": self.root,
                "entries": entries, "hits": self.hits,
                "misses": self.misses, "writes": self.writes,
                "corruptions": self.corruptions,
                "invalidations": invalidations}
