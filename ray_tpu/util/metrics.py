"""User-defined application metrics — Counter, Gauge, Histogram.

Role-equivalent of the reference's ``ray.util.metrics``
(``python/ray/util/metrics.py``): tagged metrics recorded in-process and
aggregated cluster-wide.  TPU-native simplification: instead of an
OpenCensus→agent→Prometheus pipeline, each worker keeps a local registry
and pushes deltas to the control-plane KV on record (batched); the head
exposes the merged view via ``snapshot()`` / the state API, and
``prometheus_text()`` renders the standard exposition format for scraping.
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

_REGISTRY_NS = "metrics"
_FLUSH_INTERVAL_S = 2.0

# Deliberately a RAW lock, never debug_locks.make_lock: DebugLock's own
# instrumentation records histograms through _record -> `with _lock:`,
# so an instrumented registry lock would re-enter itself and deadlock
# the process exactly when RAY_TPU_DEBUG_LOCKS=1.  This lock is a leaf
# by construction — nothing is acquired under it.
_lock = threading.Lock()
_local: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], dict] = {}
_dirty = False
_last_flush = 0.0

# Registered by processes that have no CoreWorker (the node agent): takes
# the serialized payload and pushes it to the control-plane KV its own way.
_flush_hook: Optional[Callable[[dict], None]] = None


def _tag_key(tags: Optional[Dict[str, str]]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((tags or {}).items()))


def _apply_locked(name: str, kind: str, tags, value: float, buckets=None):
    """Apply one sample to the local registry.  ``_lock`` must be held."""
    key = (name, _tag_key(tags))
    ent = _local.get(key)
    if ent is None:
        ent = {"kind": kind, "value": 0.0, "count": 0, "sum": 0.0,
               "buckets": list(buckets or []), "bucket_counts": None}
        if ent["buckets"]:
            ent["bucket_counts"] = [0] * (len(ent["buckets"]) + 1)
        _local[key] = ent
    if kind == "counter":
        ent["value"] += value
    elif kind == "gauge":
        ent["value"] = value
    else:  # histogram
        ent["count"] += 1
        ent["sum"] += value
        for i, b in enumerate(ent["buckets"]):
            if value <= b:
                ent["bucket_counts"][i] += 1
                break
        else:
            ent["bucket_counts"][-1] += 1


def _record(name: str, kind: str, tags, value: float, buckets=None):
    global _dirty
    with _lock:
        _apply_locked(name, kind, tags, value, buckets)
        _dirty = True
    _maybe_flush()


def _record_batch(entries):
    """Apply several samples under ONE lock round trip (the flight
    recorder's per-task phase set rides this so the hot path pays the
    lock once, not once per phase).  ``entries``: iterable of
    (name, kind, tags, value, buckets)."""
    global _dirty
    with _lock:
        for name, kind, tags, value, buckets in entries:
            _apply_locked(name, kind, tags, value, buckets)
        _dirty = True
    _maybe_flush()


def set_flush_hook(fn: Optional[Callable[[dict], None]]):
    """Install a custom payload push (processes without a CoreWorker, e.g.
    the node agent).  The hook receives the serialized registry payload and
    must not raise."""
    global _flush_hook
    _flush_hook = fn


def clear_flush_hook(fn: Callable[[dict], None]):
    """Remove ``fn`` if it is the installed hook (teardown-safe: a newer
    hook installed by a different owner is left alone).  Equality, not
    identity: bound methods are recreated per access, so ``is`` would
    never match and a stopped owner's hook would linger forever."""
    global _flush_hook
    if _flush_hook == fn:
        _flush_hook = None


def payload_snapshot(only_dirty: bool = False) -> Optional[dict]:
    """Serializable view of the local registry; marks it clean.  Returns
    None when nothing was ever recorded — or, with ``only_dirty``, when
    nothing changed since the last snapshot (payloads are cumulative, so
    a reader that already has the previous one loses nothing)."""
    global _dirty, _last_flush
    with _lock:
        if not _local or (only_dirty and not _dirty):
            return None
        payload = {
            f"{name}|{dict(tags)}": {
                "name": name, "tags": dict(tags), **{
                    k: v for k, v in ent.items() if k != "bucket_counts"
                },
                # Copied under the lock: the async push serializes the
                # payload later, and a live list would tear (bucket_counts
                # ahead of count/sum breaks bucket monotonicity).
                "bucket_counts": (
                    list(ent["bucket_counts"])
                    if ent["bucket_counts"] is not None else None
                ),
            }
            for (name, tags), ent in _local.items()
        }
        _dirty = False
        _last_flush = time.monotonic()
    return payload


async def _kv_put_async(w, payload: dict):
    try:
        await w.cp.call(
            "kv_put",
            {"namespace": _REGISTRY_NS, "key": f"worker:{w.worker_id.hex()}",
             "value": payload, "overwrite": True},
        )
    except Exception:  # raylint: waive[RTL003] metrics are best-effort
        pass


def _maybe_flush(force: bool = False):
    """Push this process's metric state to the control-plane KV (best
    effort).  Safe from ANY thread: called on the worker's protocol loop
    (built-in runtime metrics record there) it schedules an async push —
    a blocking ``kv_put`` would deadlock the loop on its own completion.
    A recording thread hands the push to the loop and does not wait for it
    either: the loop may be waiting for THAT thread (an engine's loop
    records each step while a unary caller blocks the replica's loop until
    its request is done).  Only ``flush()`` (``force``) waits."""
    now = time.monotonic()
    if not force and (not _dirty or now - _last_flush < _FLUSH_INTERVAL_S):
        return
    hook = _flush_hook
    w = None
    if hook is None:
        from ..core.core_worker import try_global_worker

        w = try_global_worker()
        if w is None:
            return
    payload = payload_snapshot()
    if payload is None:
        return
    try:
        if hook is not None:
            hook(payload)
            return
        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if running is not None and running is w.loop:
            running.create_task(_kv_put_async(w, payload))
        elif force:
            w.kv_put(_REGISTRY_NS, f"worker:{w.worker_id.hex()}", payload)
        else:
            asyncio.run_coroutine_threadsafe(_kv_put_async(w, payload), w.loop)
    except Exception:  # raylint: waive[RTL003] flush is best-effort and cannot count via itself
        pass


class _Metric:
    kind = ""

    def __init__(self, name: str, description: str = "",
                 tag_keys: Optional[Sequence[str]] = None):
        if not name:
            raise ValueError("metric name must be non-empty")
        self._name = name
        self._description = description
        self._tag_keys = tuple(tag_keys or ())
        self._default_tags: Dict[str, str] = {}

    @property
    def info(self) -> dict:
        return {"name": self._name, "description": self._description,
                "tag_keys": self._tag_keys, "default_tags": self._default_tags}

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _merged(self, tags):
        merged = dict(self._default_tags)
        merged.update(tags or {})
        extra = set(merged) - set(self._tag_keys)
        if extra:
            raise ValueError(f"undeclared tag keys {sorted(extra)} for {self._name}")
        return merged


class Counter(_Metric):
    kind = "counter"

    def inc(self, value: float = 1.0, tags: Optional[Dict[str, str]] = None):
        if value <= 0:
            raise ValueError("Counter.inc value must be > 0")
        _record(self._name, "counter", self._merged(tags), value)


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        _record(self._name, "gauge", self._merged(tags), float(value))


DEFAULT_HISTOGRAM_BOUNDARIES = [
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 50.0, 100.0,
]


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, description="", boundaries=None, tag_keys=None):
        super().__init__(name, description, tag_keys)
        self._boundaries = list(boundaries or DEFAULT_HISTOGRAM_BOUNDARIES)
        if sorted(self._boundaries) != self._boundaries:
            raise ValueError("histogram boundaries must be sorted ascending")

    def observe(self, value: float, tags: Optional[Dict[str, str]] = None):
        _record(self._name, "histogram", self._merged(tags), float(value),
                buckets=self._boundaries)


# ------------------------------------------------------------- aggregation
def flush():
    """Force-push local metrics to the cluster registry."""
    _maybe_flush(force=True)


def merge_payloads(payloads) -> Dict[str, dict]:
    """Merge per-process registry payloads into the cluster view
    (counters summed, gauges last-writer-wins, histograms merged).
    ``payloads``: iterable of payload dicts (one per process)."""
    merged: Dict[str, dict] = {}
    for data in payloads:
        if not data:
            continue
        for mkey, ent in data.items():
            cur = merged.get(mkey)
            if cur is None:
                merged[mkey] = dict(ent)
            elif ent["kind"] == "counter":
                cur["value"] += ent["value"]
            elif ent["kind"] == "gauge":
                cur["value"] = ent["value"]
            else:
                cur["count"] += ent["count"]
                cur["sum"] += ent["sum"]
                if cur.get("bucket_counts") and ent.get("bucket_counts"):
                    cur["bucket_counts"] = [
                        a + b for a, b in
                        zip(cur["bucket_counts"], ent["bucket_counts"])
                    ]
    return merged


def snapshot() -> Dict[str, dict]:
    """Cluster-wide merged metric view (counters summed across workers,
    gauges last-writer-wins, histograms merged)."""
    from ..core.core_worker import global_worker

    w = global_worker()
    flush()
    return merge_payloads(
        w.kv_get(_REGISTRY_NS, key) for key in w.kv_keys(_REGISTRY_NS)
    )


def _escape_label(v) -> str:
    return str(v).replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")


def _label_str(items) -> str:
    """items: sequence of (key, value) pairs -> '{k="v",...}' or ''."""
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{_escape_label(v)}"' for k, v in items) + "}"


def prometheus_text() -> str:
    """Render the merged view in Prometheus exposition format.

    Histograms emit cumulative ``_bucket`` lines with ``le`` labels
    (including ``le="+Inf"``) so scrapers can compute quantiles, and each
    metric name gets exactly ONE ``# TYPE`` line regardless of how many
    tag sets it carries (strict parsers reject duplicates)."""
    by_name: Dict[str, list] = {}
    for _mkey, ent in sorted(snapshot().items()):
        by_name.setdefault(ent["name"], []).append(ent)
    lines = []
    for name in sorted(by_name):
        ents = by_name[name]
        kind = ents[0]["kind"]
        lines.append(f"# TYPE {name} {kind}")
        for ent in ents:
            items = sorted(ent["tags"].items())
            label_s = _label_str(items)
            if ent["kind"] == "histogram":
                buckets = ent.get("buckets") or []
                counts = ent.get("bucket_counts") or []
                if buckets and len(counts) == len(buckets) + 1:
                    cum = 0
                    for b, c in zip(buckets, counts):
                        cum += c
                        le_s = _label_str(items + [("le", repr(float(b)))])
                        lines.append(f"{name}_bucket{le_s} {cum}")
                inf_s = _label_str(items + [("le", "+Inf")])
                lines.append(f"{name}_bucket{inf_s} {ent['count']}")
                lines.append(f"{name}_count{label_s} {ent['count']}")
                lines.append(f"{name}_sum{label_s} {ent['sum']}")
            else:
                lines.append(f"{name}{label_s} {ent['value']}")
    return "\n".join(lines) + ("\n" if lines else "")
