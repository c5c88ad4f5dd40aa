"""The names the program gave its operations, out of a profiler trace.

An `XLA Ops` event of a v5e trace is named by its HLO line (`%fusion.612 = ...`), and
`jax.profiler.ProfileData` shows of its stats only the three of the event itself. What
says which part of the program an operation belongs to is the `tf_op` stat of the
event's *metadata* (the instruction's `op_name`, which JAX fills with the name stack:
`jit(_train_step)/jvp(GPTLM)/layer_8/attention/pallas_call:`, the colon before an
operation type that a JAX program leaves empty), and `ProfileData` does not reach it. So this reads the `.xplane.pb` itself: protobuf's wire format, the few fields
of `XSpace`, `XPlane`, `XEventMetadata`, `XStatMetadata` and `XStat` that lead there,
the events' lines skipped unread. Nothing but the standard library."""

from __future__ import annotations

import functools
from pathlib import Path

from benchmarks import runtime
from benchmarks.trace import DEVICE_PLANE

OP_NAME_STAT = "tf_op"
# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_EVENT_METADATA_NAME, _EVENT_METADATA_STATS = 2, 5
_STAT_METADATA_ID, _STAT_METADATA_NAME = 1, 2
_STAT_METADATA_ID_OF, _STAT_STR, _STAT_BYTES, _STAT_REF = 1, 5, 6, 7


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for a varint, a memoryview for a
    length-delimited or fixed-width field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an .xplane.pb")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _plane_op_names(plane) -> dict[str, str]:
    """event name -> op_name of one plane, {} if the plane is no device's."""
    name, event_metadata, stat_names = "", [], {}
    for number, value in fields(plane):
        if number == _PLANE_NAME:
            name = _text(value)
        elif number == _PLANE_EVENT_METADATA:
            event_metadata.append(value)
        elif number == _PLANE_STAT_METADATA:
            entry = dict(fields(dict(fields(value))[_MAP_VALUE]))
            stat_names[entry.get(_STAT_METADATA_ID, 0)] = _text(entry.get(_STAT_METADATA_NAME, b""))
    if not DEVICE_PLANE.match(name):
        return {}
    wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
    out: dict[str, str] = {}
    for entry in event_metadata:
        event_name, op_name = "", ""
        for number, value in fields(dict(fields(entry))[_MAP_VALUE]):
            if number == _EVENT_METADATA_NAME:
                event_name = _text(value)
            elif number == _EVENT_METADATA_STATS:
                stat = dict(fields(value))
                if stat.get(_STAT_METADATA_ID_OF) in wanted:
                    if _STAT_REF in stat:
                        op_name = stat_names.get(stat[_STAT_REF], "")
                    else:
                        op_name = _text(stat.get(_STAT_STR, stat.get(_STAT_BYTES, b"")))
        if event_name and op_name:
            out[event_name] = op_name
    return out


@functools.lru_cache(maxsize=2)
def op_names(path: str) -> dict[str, str]:
    """event name -> op_name, over the device planes of the `.xplane.pb` at `path`; an
    event whose instruction carries no op_name (an asynchronous copy, a parameter's
    slice) is not in it."""
    out: dict[str, str] = {}
    for number, plane in fields(memoryview(Path(path).read_bytes())):
        if number == _SPACE_PLANES:
            out.update(_plane_op_names(plane))
    return out


def newest_trace(root: Path | None = None) -> Path | None:
    """The newest `.xplane.pb` under the benchmark's trace directory: in a traced run,
    the one that run wrote seconds ago. A reader's `ctx` carries no path to it."""
    return max((root or runtime.TRACE_DIR).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime,
               default=None)


def of_run(ctx: dict) -> dict[str, str]:
    """The names for a reader: `ctx["op_names"]` where a test hands them over, else
    those of the newest trace, read once for all the readers of a run."""
    if "op_names" in ctx:
        return ctx["op_names"]
    path = newest_trace()
    try:
        return op_names(str(path)) if path else {}
    except (OSError, ValueError, IndexError, KeyError):  # unreadable or cut short: no names
        return {}
