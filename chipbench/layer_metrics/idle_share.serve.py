"""Device: share of the traced window in which no operation ran on the
chip (one minus the union of the XLA ops' device intervals over the
window, averaged over the chips that ran)."""

SOURCE = "device_trace"


def applies(cell):
    return cell.traffic["driver"] == "serve"


def read(out):
    tr = out.get("trace")
    if not tr or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
