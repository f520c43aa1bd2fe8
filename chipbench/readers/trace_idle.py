"""Share of the traced job in which no op ran on the device, in percent."""


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace["devices"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
