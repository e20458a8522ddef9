"""Process-stable entry addressing for the persistent plan store.

The in-memory caches key on Python object hashes (tuples holding code
objects, interned strings, frozensets) — fast, but meaningless across
a process restart: ``hash(str)`` is randomized per process and code
objects hash by identity-adjacent fields. The on-disk store therefore
addresses entries by :func:`stable_digest` — a SHA-256 walk over the
SAME plan-key tuple ``evaluate()`` computes, with every component
reduced to its structural content:

* scalars / strings / bytes feed their type tag + value;
* tuples/lists/dicts/frozensets feed tagged, (sorted where unordered)
  recursions;
* code objects feed their bytecode, consts, names and arity — two
  processes compiling the same ``def`` digest identically;
* functions feed ``module.qualname`` (stable for module-level and
  locally-defined kernels at the same definition site);
* anything else raises :class:`UnstableKeyError` — the plan is simply
  not persistable (``persist_unstable_keys`` counts it, evaluation is
  untouched).

A digest alone must never authorize a load: :func:`env_fingerprint`
captures everything OUTSIDE the plan key that changes what a compiled
executable means — jax/jaxlib/python versions, platform, device
count, mesh shape + epoch and the optimizer-flags key (the platform
also decides the kernel backend) — and the store validates the manifest's fingerprint verbatim
on every load, so a stale or foreign entry can never alias even under
a digest collision.
"""

from __future__ import annotations

import hashlib
import sys
import types
from typing import Any, Dict, Tuple

import numpy as np

FORMAT_VERSION = 1


class UnstableKeyError(TypeError):
    """A plan-key component has no process-stable byte representation;
    the plan cannot be addressed on disk (and is not persisted)."""


def _feed(h, obj: Any) -> None:
    # type tags keep 1 and 1.0 and "1" and True apart
    if obj is None:
        h.update(b"\x00N")
    elif obj is True:
        h.update(b"\x00T")
    elif obj is False:
        h.update(b"\x00F")
    elif isinstance(obj, int):
        h.update(b"\x00i" + str(obj).encode())
    elif isinstance(obj, float):
        h.update(b"\x00f" + repr(obj).encode())
    elif isinstance(obj, str):
        b = obj.encode()
        h.update(b"\x00s" + str(len(b)).encode() + b":" + b)
    elif isinstance(obj, bytes):
        h.update(b"\x00b" + str(len(obj)).encode() + b":" + obj)
    elif isinstance(obj, (tuple, list)):
        h.update(b"\x00(" if isinstance(obj, tuple) else b"\x00[")
        for item in obj:
            _feed(h, item)
        h.update(b"\x00)")
    elif isinstance(obj, (frozenset, set)):
        h.update(b"\x00{")
        for d in sorted(stable_digest(item) for item in obj):
            h.update(d.encode())
        h.update(b"\x00}")
    elif isinstance(obj, dict):
        h.update(b"\x00d")
        for k in sorted(obj, key=lambda k: stable_digest(k)):
            _feed(h, k)
            _feed(h, obj[k])
        h.update(b"\x00e")
    elif isinstance(obj, np.dtype):
        h.update(b"\x00y" + str(obj).encode())
    elif isinstance(obj, (np.integer, np.floating, np.bool_)):
        h.update(b"\x00n" + str(obj.dtype).encode() + b":"
                 + repr(obj.item()).encode())
    elif isinstance(obj, types.CodeType):
        # structural identity, mirroring fn_key's intent: the same def
        # compiled in another process digests the same
        h.update(b"\x00c")
        _feed(h, (obj.co_name, obj.co_argcount, obj.co_kwonlyargcount,
                  obj.co_nlocals, obj.co_flags, obj.co_code,
                  obj.co_names, obj.co_varnames, obj.co_freevars,
                  obj.co_cellvars, obj.co_consts))
    elif isinstance(obj, (types.FunctionType, types.BuiltinFunctionType,
                          types.MethodType)):
        # module-qualified name: stable for module-level kernels and
        # for local defs at the same definition site
        mod = getattr(obj, "__module__", None)
        qual = getattr(obj, "__qualname__", getattr(obj, "__name__", None))
        if not mod or not qual:
            raise UnstableKeyError(
                f"unnameable callable in plan key: {obj!r}")
        h.update(b"\x00q" + f"{mod}.{qual}".encode())
    elif isinstance(obj, type):
        h.update(b"\x00t" + f"{obj.__module__}.{obj.__qualname__}".encode())
    else:
        raise UnstableKeyError(
            f"plan-key component {type(obj).__name__} has no stable "
            "byte representation; plan is not persistable")


def stable_digest(obj: Any) -> str:
    """Process-stable SHA-256 hex digest of a (nested) plan-key
    component. Raises :class:`UnstableKeyError` for components with no
    stable representation (the caller skips persistence)."""
    h = hashlib.sha256()
    _feed(h, obj)
    return h.hexdigest()[:40]


def env_fingerprint(mesh: Any) -> Dict[str, Any]:
    """Everything outside the plan key that decides whether a
    serialized executable is meaningful in THIS process. Validated
    verbatim (dict equality after a JSON round trip) on every load —
    version skew, a different platform, a foreign mesh shape or a dead
    mesh epoch can never alias a live entry. JSON-clean by
    construction."""
    import jax
    import jaxlib

    from ..parallel import mesh as mesh_mod

    # lazy: expr.base imports this package at module init; by the time
    # a fingerprint is computed the expr layer is fully loaded
    from ..expr import base as expr_base

    return {
        "format": FORMAT_VERSION,
        "python": list(sys.version_info[:3]),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
        "device_count": len(jax.devices()),
        "mesh_shape": [[str(k), int(v)]
                       for k, v in sorted(mesh.shape.items())],
        "mesh_epoch": int(mesh_mod._EPOCH),
        "opt_flags": stable_digest(expr_base._opt_flags_key()),
    }


def entry_digest(plan_key: Tuple, fingerprint: Dict[str, Any]) -> str:
    """The on-disk address of one plan: the raw-DAG plan key extended
    with the full environment fingerprint. Raises UnstableKeyError
    when the plan key cannot be stably represented."""
    return stable_digest((plan_key, fingerprint))
