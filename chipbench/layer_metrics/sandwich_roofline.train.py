"""Kernels: the butterfly sandwich kernels' share of their roofline in
the traced train steps. The least time is the larger of the sites' model
operations over the bf16 peak and their model bytes over HBM bandwidth
(forward: each token's bf16 input and output row; backward: its input
and output-gradient rows read, its input-gradient row written), per
trained token; the kernel time is the device time of the operations
named ``sandwich_matmul`` (forward and backward kernels), summed."""

SOURCE = "device_trace"
PATTERNS = ("sandwich_matmul",)


def applies(cell):
    return (cell.traffic["driver"] == "train"
            and bool(cell.config.get("butterfly")))


def read(out):
    from chipbench import model_ops, xplane

    tr, peaks = out.get("trace"), out["peaks"]
    mc = out["cell"].config
    if peaks is None or not mc.get("butterfly"):
        return None
    t = xplane.op_seconds(tr, PATTERNS)
    if t is None:
        return None
    tokens = tr["steps"] * out["cell"].traffic["trainer"]["batch"] * \
        out["cell"].traffic["trainer"]["seq_len"]
    flops = 3 * model_ops.sandwich_row_flops(mc) * tokens
    bytes_ = tokens * sum(c * ((i + o) + (2 * i + o)) * model_ops.BF16
                          for i, o, c in model_ops.sandwich_sites(mc))
    least = max(flops / peaks.bf16_flops, bytes_ / peaks.hbm_bytes_s)
    return 100.0 * least / t
