"""Dataset tokens the run walks through per second of the window: every row
of every meta-batch of every step completed in it (full rows, no padding)."""


def read(run):
    steps = run.close_step - run.open_step
    return steps * run.rows_per_step * run.cell.traffic["seq_len"] / run.window_s
