"""ctypes bindings for the native core (libkfcore.so).

Native components (SURVEY.md §2.8 ledger): work queue + expectations (the
reference's Go controller machinery) and the metadata store (the reference's
C++ MLMD server). Built on demand with `make`; sanitizer self-tests run via
`make check` (ASan/UBSan) and `make tsan`.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import weakref
from pathlib import Path

_DIR = Path(__file__).parent
_LIB_PATH = _DIR / "build" / "libkfcore.so"
#: sha256 of the sources the library was built from, written beside it
_HASH_PATH = _LIB_PATH.with_suffix(".so.srchash")
_lib = None


def _source_hash() -> str:
    """Content hash of everything that goes into libkfcore.so. Content, not
    mtimes: a checkout or a copy of the tree does not preserve those."""
    # selftest-only sources never link into the lib — not staleness signals
    selftest_only = {"selftest.cc", "tsan_clockwait_shim.cc"}
    h = hashlib.sha256()
    for src in [_DIR / "Makefile", *sorted((_DIR / "src").glob("*.cc"))]:
        if src.name not in selftest_only:
            h.update(src.name.encode() + b"\0" + src.read_bytes() + b"\0")
    return h.hexdigest()


def ensure_built() -> Path:
    """Build libkfcore.so from src/*.cc when it is missing or was built from
    other sources (build/ is not tracked: a fresh checkout always builds).
    Serialized by a lock file — pods and test workers may import the
    package at the same moment."""
    want = _source_hash()

    def fresh() -> bool:
        try:
            return _LIB_PATH.exists() and _HASH_PATH.read_text() == want
        except OSError:
            return False

    if fresh():
        return _LIB_PATH
    _LIB_PATH.parent.mkdir(exist_ok=True)
    # one open file description per caller, so the lock also serializes
    # threads of this process
    with open(_LIB_PATH.parent / ".build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not fresh():  # another caller may have built it meanwhile
            try:
                subprocess.run(
                    ["make", "-B", str(_LIB_PATH.relative_to(_DIR))],
                    cwd=_DIR, check=True, capture_output=True, text=True)
            except subprocess.CalledProcessError as exc:
                raise RuntimeError(
                    "building kubeflow_tpu/native/build/libkfcore.so "
                    f"failed:\n{exc.stderr[-2000:]}") from exc
            _HASH_PATH.write_text(want)
    return _LIB_PATH


def lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        path = ensure_built()
        L = ctypes.CDLL(str(path))
        # workqueue
        L.kf_wq_new.restype = ctypes.c_void_p
        L.kf_wq_new.argtypes = [ctypes.c_double, ctypes.c_double]
        L.kf_wq_free.argtypes = [ctypes.c_void_p]
        L.kf_wq_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_wq_add_after.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_double]
        L.kf_wq_add_rate_limited.restype = ctypes.c_double
        L.kf_wq_add_rate_limited.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_wq_forget.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_wq_num_requeues.restype = ctypes.c_int
        L.kf_wq_num_requeues.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_wq_get.restype = ctypes.c_void_p  # manual free => void_p not char_p
        L.kf_wq_get.argtypes = [ctypes.c_void_p, ctypes.c_double]
        L.kf_wq_done.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_wq_len.restype = ctypes.c_int
        L.kf_wq_len.argtypes = [ctypes.c_void_p]
        L.kf_wq_shutdown.argtypes = [ctypes.c_void_p]
        L.kf_wq_shutting_down.restype = ctypes.c_int
        L.kf_wq_shutting_down.argtypes = [ctypes.c_void_p]
        L.kf_free.argtypes = [ctypes.c_void_p]
        # reconcile driver
        L.kf_rd_new.restype = ctypes.c_void_p
        L.kf_rd_new.argtypes = [ctypes.c_void_p, ctypes.c_int, RECONCILE_CB]
        L.kf_rd_stop.argtypes = [ctypes.c_void_p]
        L.kf_rd_free.argtypes = [ctypes.c_void_p]
        for fn in ("kf_rd_total", "kf_rd_errors", "kf_rd_conflicts"):
            getattr(L, fn).restype = ctypes.c_long
            getattr(L, fn).argtypes = [ctypes.c_void_p]
        # expectations
        L.kf_exp_new.restype = ctypes.c_void_p
        L.kf_exp_new.argtypes = [ctypes.c_double]
        L.kf_exp_free.argtypes = [ctypes.c_void_p]
        L.kf_exp_expect_creations.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong]
        L.kf_exp_expect_deletions.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong]
        L.kf_exp_creation_observed.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_exp_deletion_observed.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_exp_satisfied.restype = ctypes.c_int
        L.kf_exp_satisfied.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_exp_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_exp_counts.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_longlong),
        ]
        # event hub
        L.kf_hub_new.restype = ctypes.c_void_p
        L.kf_hub_new.argtypes = [ctypes.c_int]
        L.kf_hub_free.argtypes = [ctypes.c_void_p]
        L.kf_hub_subscribe.restype = ctypes.c_longlong
        L.kf_hub_subscribe.argtypes = [ctypes.c_void_p]
        L.kf_hub_subscribe_filtered.restype = ctypes.c_longlong
        L.kf_hub_subscribe_filtered.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_hub_unsubscribe.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        L.kf_hub_publish.restype = ctypes.c_longlong
        L.kf_hub_publish.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
        ]
        L.kf_hub_publish_labeled.restype = ctypes.c_longlong
        L.kf_hub_publish_labeled.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p,
        ]
        L.kf_hub_poll.restype = ctypes.c_int
        L.kf_hub_poll.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_double,
            ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ]
        L.kf_hub_backlog.restype = ctypes.c_int
        L.kf_hub_backlog.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        # metastore
        L.kf_ms_open.restype = ctypes.c_void_p
        L.kf_ms_open.argtypes = [ctypes.c_char_p]
        L.kf_ms_close.argtypes = [ctypes.c_void_p]
        L.kf_ms_put_artifact.restype = ctypes.c_longlong
        L.kf_ms_put_artifact.argtypes = [ctypes.c_void_p, ctypes.c_longlong] + [ctypes.c_char_p] * 4
        L.kf_ms_put_execution.restype = ctypes.c_longlong
        L.kf_ms_put_execution.argtypes = [ctypes.c_void_p, ctypes.c_longlong] + [ctypes.c_char_p] * 4
        L.kf_ms_put_event.restype = ctypes.c_int
        L.kf_ms_put_event.argtypes = [ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong, ctypes.c_int]
        for fn in ("kf_ms_get_artifact", "kf_ms_get_execution"):
            getattr(L, fn).restype = ctypes.c_void_p
            getattr(L, fn).argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        for fn in ("kf_ms_list_artifacts", "kf_ms_list_executions"):
            getattr(L, fn).restype = ctypes.c_void_p
            getattr(L, fn).argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        L.kf_ms_events.restype = ctypes.c_void_p
        L.kf_ms_events.argtypes = [ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong]
        _lib = L
    return _lib


# int cb(const char* key, double* requeue_after_s) — see reconciler.cc for
# the 0/1/2 (ok/conflict/error) contract. ctypes acquires the GIL when the
# C++ worker threads invoke it.
RECONCILE_CB = ctypes.CFUNCTYPE(
    ctypes.c_int, ctypes.c_char_p, ctypes.POINTER(ctypes.c_double)
)


def _finalize_driver(L: ctypes.CDLL, h: int, cb) -> None:
    """Join + free a native driver. Runs via weakref.finalize — at GC of the
    wrapper OR at interpreter exit, whichever comes first — because a C++
    worker invoking the ctypes trampoline after the CFUNCTYPE object (or the
    interpreter) is gone is undefined behavior. `cb` is carried solely to
    keep the trampoline alive until the workers are joined. ctypes releases
    the GIL during kf_rd_stop, so in-flight callbacks can finish."""
    del cb  # alive until here — that's its whole job
    try:
        L.kf_rd_stop(h)
        L.kf_rd_free(h)
    except Exception:  # noqa: BLE001 — teardown must not raise
        pass


class ReconcileDriver:
    """Native worker pool draining a WorkQueue through a Python reconcile
    callback (reconciler.cc). C++ owns the threads and the full requeue
    discipline; the callback is the only Python on the hot path."""

    def __init__(self, wq: "WorkQueue", n_workers: int, callback):
        self._L = lib()
        # the CFUNCTYPE object must outlive the driver or C++ calls a
        # collected trampoline; _finalize_driver holds it until join
        self._cb = callback if isinstance(callback, RECONCILE_CB) else RECONCILE_CB(callback)
        self._h = self._L.kf_rd_new(wq._h, n_workers, self._cb)
        self._fin = weakref.finalize(
            self, _finalize_driver, self._L, self._h, self._cb
        )

    def stop(self) -> None:
        """Joins the workers (idempotent; the handle stays valid for metric
        reads). Shut the queue down first for a prompt join."""
        if self._h:
            self._L.kf_rd_stop(self._h)

    @property
    def total(self) -> int:
        return self._L.kf_rd_total(self._h) if self._h else 0

    @property
    def errors(self) -> int:
        return self._L.kf_rd_errors(self._h) if self._h else 0

    @property
    def conflicts(self) -> int:
        return self._L.kf_rd_conflicts(self._h) if self._h else 0

    def close(self) -> None:
        """Join + free now (equivalent to GC/exit finalization)."""
        if self._h:
            self._fin()
            self._h = None


def _take_string(ptr: int | None) -> str | None:
    """Copy a malloc'd C string and free it."""
    if not ptr:
        return None
    L = lib()
    s = ctypes.string_at(ptr).decode()
    L.kf_free(ptr)
    return s


class WorkQueue:
    """Rate-limited delaying work queue (client-go workqueue semantics)."""

    def __init__(self, base_delay_s: float = 0.005, max_delay_s: float = 60.0):
        self._L = lib()
        self._h = self._L.kf_wq_new(base_delay_s, max_delay_s)

    def add(self, key: str) -> None:
        self._L.kf_wq_add(self._h, key.encode())

    def add_after(self, key: str, delay_s: float) -> None:
        self._L.kf_wq_add_after(self._h, key.encode(), delay_s)

    def add_rate_limited(self, key: str) -> float:
        return self._L.kf_wq_add_rate_limited(self._h, key.encode())

    def forget(self, key: str) -> None:
        self._L.kf_wq_forget(self._h, key.encode())

    def num_requeues(self, key: str) -> int:
        return self._L.kf_wq_num_requeues(self._h, key.encode())

    def get(self, timeout_s: float = -1.0) -> str | None:
        return _take_string(self._L.kf_wq_get(self._h, timeout_s))

    def done(self, key: str) -> None:
        self._L.kf_wq_done(self._h, key.encode())

    def __len__(self) -> int:
        return self._L.kf_wq_len(self._h)

    def shutdown(self) -> None:
        self._L.kf_wq_shutdown(self._h)

    @property
    def shutting_down(self) -> bool:
        return bool(self._L.kf_wq_shutting_down(self._h))

    def close(self) -> None:
        if self._h:
            self._L.kf_wq_free(self._h)
            self._h = None


class Expectations:
    """ControllerExpectations: duplicate-action guard for reconcilers."""

    def __init__(self, ttl_s: float = 300.0):
        self._L = lib()
        self._h = self._L.kf_exp_new(ttl_s)

    def expect_creations(self, key: str, n: int) -> None:
        self._L.kf_exp_expect_creations(self._h, key.encode(), n)

    def expect_deletions(self, key: str, n: int) -> None:
        self._L.kf_exp_expect_deletions(self._h, key.encode(), n)

    def creation_observed(self, key: str) -> None:
        self._L.kf_exp_creation_observed(self._h, key.encode())

    def deletion_observed(self, key: str) -> None:
        self._L.kf_exp_deletion_observed(self._h, key.encode())

    def satisfied(self, key: str) -> bool:
        return bool(self._L.kf_exp_satisfied(self._h, key.encode()))

    def delete(self, key: str) -> None:
        self._L.kf_exp_delete(self._h, key.encode())

    def counts(self, key: str) -> tuple[int, int]:
        a = ctypes.c_longlong()
        d = ctypes.c_longlong()
        self._L.kf_exp_counts(self._h, key.encode(), ctypes.byref(a), ctypes.byref(d))
        return a.value, d.value

    def close(self) -> None:
        if self._h:
            self._L.kf_exp_free(self._h)
            self._h = None


class EventHub:
    """Broadcast hub with bounded per-subscriber buffers (informer fan-out).

    poll() returns (rc, seq, etype, kind, key): rc 0 = event, 1 = timeout,
    2 = subscriber overflowed (cleared — relist), 3 = unknown subscriber.
    """

    EVENT, EMPTY, OVERFLOWED, GONE = 0, 1, 2, 3

    def __init__(self, capacity: int = 4096):
        self._L = lib()
        self._h = self._L.kf_hub_new(capacity)
        self.capacity = capacity

    @staticmethod
    def _esc(s: str) -> str:
        """Escape the filter-spec/CSV metacharacters in a label key or
        value. Applied identically on the publish and subscribe sides, so
        the hub's equality match compares consistently-ENCODED strings —
        C++ never needs to decode, and a value like "x,app=b" can neither
        forge nor hide a selector match."""
        return (s.replace("%", "%25").replace(",", "%2C")
                .replace(";", "%3B").replace(":", "%3A")
                .replace("=", "%3D"))

    @classmethod
    def filter_spec(cls, filters) -> str:
        """Render {kind: selector | None} to the native filter string
        ("kind[:k[=v][,k2]];..."). selector = {label: value | None};
        a None value means "label present, any value"."""
        parts = []
        for kind, sel in filters.items():
            if sel:
                terms = ",".join(
                    cls._esc(k) if v is None
                    else f"{cls._esc(k)}={cls._esc(v)}"
                    for k, v in sorted(sel.items()))
                parts.append(f"{kind}:{terms}")
            else:
                parts.append(kind)
        return ";".join(parts)

    def subscribe(self, kinds=None, filters=None) -> int:
        """Subscribe; ``filters`` ({kind: label-selector-or-None}) or
        ``kinds`` (iterable — every kind unfiltered) installs a
        server-side filter: events outside it are never buffered for this
        subscriber, so they can neither overflow it nor cost it work."""
        if filters is None and kinds:
            filters = {k: None for k in kinds}
        if not filters:
            return self._L.kf_hub_subscribe(self._h)
        return self._L.kf_hub_subscribe_filtered(
            self._h, self.filter_spec(filters).encode())

    def unsubscribe(self, sub_id: int) -> None:
        self._L.kf_hub_unsubscribe(self._h, sub_id)

    def publish(self, etype: int, kind: str, key: str,
                labels: dict | None = None) -> int:
        if labels:
            csv = ",".join(f"{self._esc(k)}={self._esc(v)}"
                           for k, v in labels.items())
            return self._L.kf_hub_publish_labeled(
                self._h, etype, kind.encode(), key.encode(), csv.encode())
        return self._L.kf_hub_publish(self._h, etype, kind.encode(), key.encode())

    def poll(self, sub_id: int, timeout_s: float):
        seq = ctypes.c_longlong()
        etype = ctypes.c_int()
        kind = ctypes.c_void_p()
        key = ctypes.c_void_p()
        rc = self._L.kf_hub_poll(
            self._h, sub_id, timeout_s,
            ctypes.byref(seq), ctypes.byref(etype),
            ctypes.byref(kind), ctypes.byref(key),
        )
        if rc != 0:
            return rc, 0, 0, None, None
        return rc, seq.value, etype.value, _take_string(kind.value), _take_string(key.value)

    def backlog(self, sub_id: int) -> int:
        return self._L.kf_hub_backlog(self._h, sub_id)

    def close(self) -> None:
        if self._h:
            self._L.kf_hub_free(self._h)
            self._h = None

    def __del__(self):  # clusters are created per test; don't leak the hub
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


_FS, _RS = "\x1f", "\x1e"


def _unescape(s: str) -> str:
    out, i = [], 0
    while i < len(s):
        c = s[i]
        if c == "\\" and i + 1 < len(s):
            i += 1
            out.append({"\\": "\\", "n": "\n", "f": _FS, "r": _RS}.get(s[i], s[i]))
        else:
            out.append(c)
        i += 1
    return "".join(out)


def _parse_records(raw: str | None, fields: list[str]) -> list[dict]:
    if not raw:
        return []
    out = []
    for rec in raw.split(_RS):
        vals = [_unescape(f) for f in rec.split(_FS)]
        if len(vals) == len(fields):
            out.append(dict(zip(fields, vals)))
    return out


_ARTIFACT_FIELDS = ["id", "type", "name", "uri", "props", "ts"]
_EXECUTION_FIELDS = ["id", "type", "name", "state", "props", "ts"]
_EVENT_FIELDS = ["execution_id", "artifact_id", "direction", "ts"]


class MetadataStore:
    """Lineage store (MLMD analogue): artifacts, executions, events."""

    INPUT, OUTPUT = 0, 1

    def __init__(self, path: str):
        self._L = lib()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._h = self._L.kf_ms_open(path.encode())

    def put_artifact(
        self, type: str, name: str, uri: str = "", props: str = "{}", id: int = 0
    ) -> int:
        return self._L.kf_ms_put_artifact(
            self._h, id, type.encode(), name.encode(), uri.encode(), props.encode()
        )

    def put_execution(
        self, type: str, name: str, state: str = "NEW", props: str = "{}", id: int = 0
    ) -> int:
        return self._L.kf_ms_put_execution(
            self._h, id, type.encode(), name.encode(), state.encode(), props.encode()
        )

    def put_event(self, execution_id: int, artifact_id: int, direction: int) -> None:
        rc = self._L.kf_ms_put_event(self._h, execution_id, artifact_id, direction)
        if rc != 0:
            raise KeyError(
                f"unknown execution {execution_id} or artifact {artifact_id}"
            )

    def get_artifact(self, id: int) -> dict | None:
        recs = _parse_records(
            _take_string(self._L.kf_ms_get_artifact(self._h, id)), _ARTIFACT_FIELDS
        )
        return recs[0] if recs else None

    def get_execution(self, id: int) -> dict | None:
        recs = _parse_records(
            _take_string(self._L.kf_ms_get_execution(self._h, id)), _EXECUTION_FIELDS
        )
        return recs[0] if recs else None

    def list_artifacts(self, type: str = "") -> list[dict]:
        return _parse_records(
            _take_string(self._L.kf_ms_list_artifacts(self._h, type.encode())),
            _ARTIFACT_FIELDS,
        )

    def list_executions(self, type: str = "") -> list[dict]:
        return _parse_records(
            _take_string(self._L.kf_ms_list_executions(self._h, type.encode())),
            _EXECUTION_FIELDS,
        )

    def events(self, execution_id: int = 0, artifact_id: int = 0) -> list[dict]:
        return _parse_records(
            _take_string(self._L.kf_ms_events(self._h, execution_id, artifact_id)),
            _EVENT_FIELDS,
        )

    def close(self) -> None:
        if self._h:
            self._L.kf_ms_close(self._h)
            self._h = None
