"""train_step.mfu: model FLOPs of the window's steps over the window's wall
time, as a percent of the chip's int8 peak (the dense units' MACs are
int8, and no implementation beats that peak).  Host clock."""


def read(rec):
    if not rec.get("peaks") or rec["window_s"] <= 0:
        return None
    return 100.0 * rec["flops"] / rec["window_s"] / rec["peaks"]["int8_ops_per_s"]
