"""Versioned model artifacts — pickle-free predictor persistence.

``DIPPM.save`` used to pickle ``{params, cfg}``; a serving process
loading that file executes arbitrary code if the artifact is tampered
with, and the format is opaque to anything but this Python process. The
v2+ artifact is a single ``.npz`` file (a zip, so one deployable blob)
holding:

* ``__dippm_artifact__`` — a UTF-8 JSON header (stored as a uint8
  array: npz carries arrays, and this keeps the whole file loadable
  with ``allow_pickle=False``) with a ``schema`` / ``schema_version``
  pair, the full :class:`~repro.core.gnn.PMGNSConfig` as plain JSON, a
  per-leaf manifest (key → shape/dtype/encoding), and caller metadata;
* one array entry per parameter leaf, keyed ``params/<path>`` with
  ``/``-joined pytree paths (``params/gnn/b0/self/w``).

Schema v3 adds **weight-compression encodings**, selected by the
``precision`` argument (``cfg.precision == "int8-weights"`` is the only
runtime policy that implies an encoding by default; a runtime ``"bf16"``
cfg stores weights f32 — see :func:`save_artifact`):

* ``"bf16"`` — floating leaves are rounded to bfloat16 and stored as a
  ``uint16`` bit view (npz has no native bfloat16, and a raw-bytes
  entry would need pickle; the view keeps ``allow_pickle=False``).
  Halves the artifact's parameter bytes; the loader views the bits
  back and upcasts to float32.
* ``"int8"`` (``precision="int8-weights"``) — ≥2-D floating leaves are
  block-quantized to int8 with per-row float32 scales
  (``repro.runtime.compression.int8_compress``); the scale rides as a
  sibling entry ``params/<path>::scale``. ~4× smaller weights; the
  loader dequantizes back to float32, so runtime numerics stay f32.

Leaves without an ``encoding`` in the manifest are stored/loaded
verbatim — which is exactly the v2 format, so v2 files keep loading
byte-for-byte. Loading never unpickles: :func:`load_artifact` reads
with ``allow_pickle=False``, validates the schema version, and rebuilds
the nested params dict from the manifest. Legacy pickle files (schema
v1) still load through an explicit **deprecated fallback** that warns —
migrate by re-saving.
"""
from __future__ import annotations

import dataclasses
import json
import warnings
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..core.gnn import PMGNSConfig

__all__ = ["save_artifact", "load_artifact", "ARTIFACT_SCHEMA",
           "ARTIFACT_VERSION"]

ARTIFACT_SCHEMA = "repro.dippm.artifact"
ARTIFACT_VERSION = 3

_PARAM_PREFIX = "params/"
_SCALE_SUFFIX = "::scale"


def _flatten(tree, prefix: str, out: Dict[str, np.ndarray]) -> None:
    if isinstance(tree, dict):
        for k in sorted(tree):
            key = str(k)
            if "/" in key:
                raise ValueError(
                    f"param key {key!r} contains '/', which is the "
                    f"artifact path separator")
            _flatten(tree[k], f"{prefix}{key}/", out)
        return
    out[prefix[:-1]] = np.asarray(tree)         # drop the trailing '/'


def _unflatten(flat: Dict[str, np.ndarray]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for key, leaf in flat.items():
        node = tree
        parts = key.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return tree


def _encode_leaf(key: str, v: np.ndarray, precision: str,
                 arrays: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """Store one leaf into ``arrays`` and return its manifest entry."""
    spec: Dict[str, Any] = {"shape": list(v.shape), "dtype": str(v.dtype)}
    floating = np.issubdtype(v.dtype, np.floating)
    if precision == "bf16" and floating:
        import ml_dtypes
        arrays[_PARAM_PREFIX + key] = (
            v.astype(ml_dtypes.bfloat16).view(np.uint16))
        spec["encoding"] = "bf16"
    elif precision == "int8-weights" and floating and v.ndim >= 2:
        from ..runtime.compression import int8_compress
        q, scale = int8_compress(v)
        arrays[_PARAM_PREFIX + key] = np.asarray(q)
        arrays[_PARAM_PREFIX + key + _SCALE_SUFFIX] = np.asarray(scale)
        spec["encoding"] = "int8"
    else:
        arrays[_PARAM_PREFIX + key] = v
    return spec


def _decode_leaf(key: str, spec: Dict[str, Any], z) -> np.ndarray:
    """Rebuild one leaf from its npz entries per the manifest encoding."""
    arr = z[_PARAM_PREFIX + key]
    enc = spec.get("encoding")
    if enc == "bf16":
        import ml_dtypes
        arr = arr.view(ml_dtypes.bfloat16).astype(np.float32)
    elif enc == "int8":
        from ..runtime.compression import int8_decompress
        scale = z[_PARAM_PREFIX + key + _SCALE_SUFFIX]
        arr = np.asarray(int8_decompress(arr, scale))
    elif enc is not None:
        raise ValueError(f"unknown artifact encoding {enc!r} for {key}")
    if list(arr.shape) != list(spec["shape"]):
        raise ValueError(
            f"artifact corrupt: {key} has shape {arr.shape}, "
            f"manifest says {spec['shape']}")
    return arr


def save_artifact(path: str, params, cfg: PMGNSConfig,
                  metadata: Optional[Dict[str, Any]] = None,
                  precision: Optional[str] = None) -> str:
    """Write a v3 artifact (npz params + JSON header) to ``path``.

    ``params`` is the PMGNS pytree (nested dicts of arrays; device
    arrays are pulled to host). ``metadata`` is free-form JSON-able
    caller context (training run id, dataset hash, ...). ``precision``
    selects the weight encoding (``f32`` verbatim, ``bf16`` half-size,
    ``int8-weights`` quarter-size weights — see module docstring).

    The default follows ``cfg.precision`` only for ``int8-weights``
    (that policy *is* artifact-level quantization). A runtime
    ``cfg.precision == "bf16"`` stores weights **f32 verbatim**: the
    bf16 policy compresses request staging, not parameters — rounding
    the stored weights too costs ~1.9 % MAPE vs ~0.4 % (see
    ``PMGNSConfig.precision``), so it never happens implicitly. Pass
    ``precision="bf16"`` explicitly for half-size rounded weights.
    Returns ``path``. The exact path is used — no ``.npz`` suffix is
    appended.
    """
    if precision is None:
        cfg_policy = getattr(cfg, "precision", "f32")
        precision = "int8-weights" if cfg_policy == "int8-weights" else "f32"
    if precision not in ("f32", "bf16", "int8-weights"):
        raise ValueError(
            f"precision must be f32|bf16|int8-weights, got {precision!r}")
    flat: Dict[str, np.ndarray] = {}
    _flatten(params, "", flat)
    arrays: Dict[str, np.ndarray] = {}
    manifest = {k: _encode_leaf(k, v, precision, arrays)
                for k, v in flat.items()}
    doc = {
        "schema": ARTIFACT_SCHEMA,
        "schema_version": ARTIFACT_VERSION,
        "cfg": dataclasses.asdict(cfg),
        "precision": precision,
        "metadata": dict(metadata or {}),
        "params": manifest,
    }
    header = np.frombuffer(json.dumps(doc).encode("utf-8"), dtype=np.uint8)
    with open(path, "wb") as f:
        np.savez(f, __dippm_artifact__=header, **arrays)
    return path


def _config_from_dict(doc: Dict[str, Any]) -> PMGNSConfig:
    """A saved config back as a :class:`PMGNSConfig`. Keys this library
    does not know are dropped, except the retired ``sparse_mp`` alias:
    set, with ``layout`` left ``"auto"``, it meant the sparse training
    layout, and the config keeps it."""
    doc = dict(doc)
    if doc.pop("sparse_mp", False) and doc.get("layout", "auto") == "auto":
        doc["layout"] = "sparse"
    known = {f.name for f in dataclasses.fields(PMGNSConfig)}
    return PMGNSConfig(**{k: v for k, v in doc.items() if k in known})


def _load_pickle_fallback(path: str) -> Tuple[Dict, PMGNSConfig, Dict]:
    """Deprecated v1 loader: the legacy ``DIPPM.save`` pickle blob."""
    import pickle
    warnings.warn(
        f"{path} is a legacy pickle predictor (artifact schema v1): "
        f"loading it executes pickle and is deprecated — re-save with "
        f"DIPPM.save / save_artifact to migrate to the npz format",
        DeprecationWarning, stacklevel=3)
    with open(path, "rb") as f:
        blob = pickle.load(f)
    return blob["params"], _config_from_dict(vars(blob["cfg"])), {
        "schema_version": 1, "format": "pickle"}


def load_artifact(path: str) -> Tuple[Dict, PMGNSConfig, Dict[str, Any]]:
    """Load an artifact → ``(params, cfg, metadata)``.

    v2/v3 files load with ``allow_pickle=False`` (no code execution);
    encoded leaves (bf16 bit views, int8 + per-row scales) decode back
    to float32 per the manifest. Anything that isn't a zip falls back
    to the deprecated v1 pickle loader with a ``DeprecationWarning``.
    Unknown schemas or a ``schema_version`` newer than this library
    raise ``ValueError``.
    """
    with open(path, "rb") as f:
        magic = f.read(2)
    if magic != b"PK":                          # not a zip → legacy pickle
        return _load_pickle_fallback(path)
    with np.load(path, allow_pickle=False) as z:
        if "__dippm_artifact__" not in z.files:
            raise ValueError(
                f"{path} is an npz without an artifact header — not a "
                f"DIPPM artifact")
        doc = json.loads(bytes(z["__dippm_artifact__"]).decode("utf-8"))
        if doc.get("schema") != ARTIFACT_SCHEMA:
            raise ValueError(
                f"unknown artifact schema {doc.get('schema')!r} "
                f"(expected {ARTIFACT_SCHEMA!r})")
        version = doc.get("schema_version")
        if not isinstance(version, int) or version > ARTIFACT_VERSION:
            raise ValueError(
                f"artifact schema_version {version!r} is newer than this "
                f"library supports (≤ {ARTIFACT_VERSION}) — upgrade repro")
        manifest = doc.get("params", {})
        flat = {key: _decode_leaf(key, spec, z)
                for key, spec in manifest.items()}
    return _unflatten(flat), _config_from_dict(doc.get("cfg", {})), dict(
        doc.get("metadata", {}))
