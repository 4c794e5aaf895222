"""Model FLOP/s utilization of the whole step: tokens a second of this
run's window times the operations a token needs (``flops.train_flops_per_
token``: forward + backward matmuls with the head, causal attention, no
recomputation, no embedding lookup) over chips times the bf16 peak."""
import flops


def read(run):
    if not run.get("tokens") or run["rehearse"]:
        return None
    per_token = flops.train_flops_per_token(run["cfg"],
                                            run["workload"]["seq"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * rate * per_token / (run["chips"]
                                       * run["peak"]["bf16_flops"])
