"""Device: share of the traced train steps' window in which no operation
ran on the chip."""

SOURCE = "device_trace"


def applies(cell):
    return cell.traffic["driver"] == "train"


def read(out):
    tr = out.get("trace")
    if not tr or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
