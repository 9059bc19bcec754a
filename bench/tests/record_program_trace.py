"""Record the small trace `data/program.xplane.pb` that
`test_program_trace.py` reads: a tiny model served through `Gateway` with
a `Trace` attached under the profiler, so that the program's own
`serve.*` host annotations lie beside the device's programs. Two requests
are admitted and decoded for a few steps inside a host annotation named
`bench.window`, as the harness names its window. The model has one layer
and the profiler records no Python frames and no HLO, which keeps the
file small.

    python3 bench/tests/record_program_trace.py <out_dir>

Run it on the chip; it prints the planes and lines it recorded, the
file's size, and the clock offset and idle split the test reads. The file
is written without the profile's `/host:metadata` plane (the programs'
HLO) and with each device op's metadata cut to its name before " = ",
which is all a reader of it uses.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

# protobuf field numbers of the profile (tsl/profiler/protobuf/xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA = 2, 4
MAP_VALUE = 2
METADATA_ID, METADATA_NAME, METADATA_CHILD = 1, 2, 6


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _encode_varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        if not value:
            return bytes(out + bytes([byte]))
        out.append(byte | 0x80)


def _fields(buf: bytes):
    """(number, payload or None, raw bytes) of each field of a message;
    the payload of a length-delimited field, None for other wire types."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        payload = None
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(buf, i)
            payload = buf[i:i + n]
            i += n
        else:
            raise ValueError(f"wire type {wire} in a profile")
        yield key >> 3, payload, buf[start:i]


def _field(number: int, payload: bytes) -> bytes:
    return (_encode_varint(number << 3 | 2) + _encode_varint(len(payload))
            + payload)


def _short_metadata(entry: bytes) -> bytes:
    """An event-metadata map entry with the op's name cut before " = "
    and without its HLO, display name and stats."""
    out = b""
    for number, payload, raw in _fields(entry):
        if number != MAP_VALUE:
            out += raw
            continue
        meta = b""
        for n, p, r in _fields(payload):
            if n == METADATA_NAME:
                meta += _field(n, p.split(b" = ")[0])
            elif n in (METADATA_ID, METADATA_CHILD):
                meta += r
        out += _field(MAP_VALUE, meta)
    return out


def shrink(buf: bytes, device_prefix: str) -> bytes:
    """A serialized profile without the `/host:metadata` plane and with
    each device plane's event metadata cut by `_short_metadata`."""
    out = b""
    for number, payload, raw in _fields(buf):
        if number != SPACE_PLANES:
            out += raw
            continue
        name = next((p for n, p, _ in _fields(payload) if n == PLANE_NAME),
                    b"").decode()
        if name == "/host:metadata":
            continue
        if not name.startswith(device_prefix):
            out += raw
            continue
        plane = b"".join(
            _field(n, _short_metadata(p)) if n == PLANE_EVENT_METADATA
            else r for n, p, r in _fields(payload))
        out += _field(SPACE_PLANES, plane)
    return out


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from bench import xplane
    from bench.metrics._program_trace import program_idle
    from repro.configs import REDUCED
    from repro.dispatch.trace import Trace
    from repro.models import Shardings, init_params
    from repro.serve import Gateway, GatewayRequest, Request, ServeEngine

    out_dir = pathlib.Path(out)
    prof = out_dir / "profile"
    shutil.rmtree(prof, ignore_errors=True)

    cfg = dataclasses.replace(REDUCED["granite-3-8b"], n_layers=1)
    params = init_params(jax.random.PRNGKey(0), cfg, Shardings(None))
    engine = ServeEngine(cfg, params, batch_slots=2, max_len=64)
    gateway = Gateway(engine, queue_capacity=4, pos_bucket=64)
    gateway.prewarm([8])
    engine.serve([Request(-1, jnp.zeros((8,), jnp.int32), 2)])
    reqs = [GatewayRequest(rid=i, prompt=jnp.arange(8, dtype=jnp.int32) + i,
                           max_new_tokens=4) for i in range(2)]
    gateway.attach_tracer(Trace("record"))
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(prof), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for g in reqs:
            gateway.submit(g)
        while len(gateway.queue) or gateway.running:
            gateway.admit_pending()
            if engine.n_slots - engine.n_free > 0:
                gateway.step()
    jax.profiler.stop_trace()
    gateway.attach_tracer(None)

    path = xplane.find_xplane(str(prof))
    dst = out_dir / "program.xplane.pb"
    dst.write_bytes(shrink(pathlib.Path(path).read_bytes(),
                           xplane.DEVICE_PREFIX))
    for plane in ProfileData.from_file(str(dst)).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", line.name, len(evs),
                  sorted({e.name for e in evs})[:8])
    pi = program_idle(xplane.read_events(str(dst)))
    print(json.dumps({"bytes": dst.stat().st_size,
                      "tokens": [g.out_tokens for g in reqs],
                      "program_idle": pi and vars(pi)}, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
