"""Writes ``small_trace.xplane.pb``: a hand-made XSpace in the wire format of
tsl's xplane.proto, small enough to work its sums out by hand
(``test_trace.py`` holds them).  Run once; the file is committed.

One chip, ``/device:TPU:0``, line ``XLA Ops`` starting at 1,000 ns:

    offset ps  duration ps  scope (tf_op)                                  category
        0        4,000,000  jit(fn)/jvp(Convolution:c0_fwd)/conv:           convolution fusion
    4,000,000    2,000,000  jit(fn)/transpose(jvp(Convolution:c0_fwd))/conv: convolution fusion
    5,000,000      500,000  jit(fn)/transpose(jvp(Convolution:c0_fwd))/mul:  loop fusion   (nested in the one above)
    8,000,000    1,000,000  jit(fn)/Optimizer::SGD/add:                      loop fusion
   10,000,000    1,000,000  (none)                                           copy-done

and the host plane with the spans perf:dispatch over [1,006,000,000 ps,
1,008,500,000 ps) and perf:fetch over [1,009,000,000 ps, 1,010,000,000 ps).
"""
import os


def varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(no, value):
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint((no << 3) | 2) + varint(len(value)) + value


def entry(key, message):
    return field(1, key) + field(2, message)


STAT_NAMES = {1: "tf_op", 2: "hlo_category"}
OPS = [
    (1, "%fusion.1 = conv", "jit(fn)/jvp(Convolution:c0_fwd)/conv:", "convolution fusion", 0, 4_000_000),
    (2, "%fusion.2 = conv", "jit(fn)/transpose(jvp(Convolution:c0_fwd))/conv:", "convolution fusion", 4_000_000, 2_000_000),
    (3, "%fusion.3 = mul", "jit(fn)/transpose(jvp(Convolution:c0_fwd))/mul:", "loop fusion", 5_000_000, 500_000),
    (4, "%fusion.4 = add", "jit(fn)/Optimizer::SGD/add:", "loop fusion", 8_000_000, 1_000_000),
    (5, "%copy-done.1", None, "copy-done", 10_000_000, 1_000_000),
]
SPANS = [(1, "perf:dispatch", 6_000_000, 2_500_000),
         (2, "perf:fetch", 9_000_000, 1_000_000)]


def device_plane():
    out = field(1, 1) + field(2, "/device:TPU:0")
    events = b""
    for mid, _, _, _, off, dur in OPS:
        events += field(4, field(1, mid) + field(2, off) + field(3, dur))
    out += field(3, field(1, 1) + field(2, "XLA Ops") + field(3, 1000) + events)
    out += field(3, field(1, 2) + field(2, "XLA Modules") + field(3, 1000)
                 + field(4, field(1, 6) + field(2, 0) + field(3, 11_000_000)))
    for mid, name, scope, cat, _, _ in OPS:
        stats = b""
        if scope:
            stats += field(5, field(1, 1) + field(5, scope))
        stats += field(5, field(1, 2) + field(5, cat))
        out += field(4, entry(mid, field(1, mid) + field(2, name) + stats))
    out += field(4, entry(6, field(1, 6) + field(2, "jit_fn(1)")))
    for sid, name in STAT_NAMES.items():
        out += field(5, entry(sid, field(1, sid) + field(2, name)))
    return out


def host_plane():
    out = field(1, 2) + field(2, "/host:CPU")
    events = b""
    for mid, _, off, dur in SPANS:
        events += field(4, field(1, mid) + field(2, off) + field(3, dur))
    out += field(3, field(1, 1) + field(2, "main/1") + field(3, 1000) + events)
    for mid, name, _, _ in SPANS:
        out += field(4, entry(mid, field(1, mid) + field(2, name)))
    return out


def build():
    return field(1, device_plane()) + field(1, host_plane())


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    os.makedirs(os.path.join(here, "small_trace"), exist_ok=True)
    with open(os.path.join(here, "small_trace", "small_trace.xplane.pb"), "wb") as f:
        f.write(build())
