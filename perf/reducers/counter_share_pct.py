"""One series' share of two series of a counter of the program's telemetry
(``mxnet_tpu.telemetry.value``; the loop turns telemetry on before the first
bind): ``params.counter`` names the counter, ``params.part`` and
``params.rest`` the labels of the two series, and the reading is
``100 * part / (part + rest)`` of their values at the end of the run.  For
counts taken while a program is traced, which a second trace of the same
program doubles on both sides.  Nothing counted on either side (a program
without the counter, a cell whose path never reaches it): nothing to read."""


def read(ctx, params):
    del ctx
    try:
        from mxnet_tpu import telemetry
        part = telemetry.value(params["counter"], **params["part"])
        rest = telemetry.value(params["counter"], **params["rest"])
    except Exception:  # noqa: BLE001 -- a program that counts otherwise
        return None
    if not part + rest:
        return None
    return 100.0 * part / (part + rest)
