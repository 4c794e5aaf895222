"""Seconds of the stage ``mx:train_step.init``
(``JitTrainStep._ensure_init`` past its early return: rules, and for every
parameter the copy, the optimizer's state, the placement), the small
programs built under it included."""
import setup_reduce


def read(run):
    return setup_reduce.read(run, "setup_part_s.step_init")
