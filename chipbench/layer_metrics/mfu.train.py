"""Train step, whole: model operations per trained token (forward and
backward, ``chipbench.model_ops``, no recomputation counted) times the
traced run's trained tokens per second, over the chip's bf16 peak."""

SOURCE = "host_clock"


def applies(cell):
    return cell.traffic["driver"] == "train"


def read(out):
    from chipbench import model_ops

    peaks = out["peaks"]
    if peaks is None:
        return None
    tr = out["cell"].traffic["trainer"]
    flops = model_ops.train_flops_per_token(out["cell"].config,
                                            tr["seq_len"])
    return 100.0 * flops * out["e2e"]["train_tok_s"] / peaks.bf16_flops
