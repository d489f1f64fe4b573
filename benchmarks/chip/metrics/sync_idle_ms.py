"""sync_idle_ms: chip-idle time per traced step, outside every program
run, that falls under the loop's host spans ``train.loss_wait`` (the
``float(loss)`` sync), ``train.control`` (heartbeat, control-loop pump,
reconfig poll) and ``train.readmit``, the mean over the cell's chips
(``chipbench.spans``)."""
from chipbench import spans


def read(rec):
    return spans.idle_ms(rec, ("train.loss_wait", "train.control",
                               "train.readmit"))
