"""data_idle_ms: chip-idle time per traced step, outside every program
run, that falls under the loop's host span ``train.data`` (the batch's
queue wait and ``device_put``), the mean over the cell's chips
(``chipbench.spans``)."""
from chipbench import spans


def read(rec):
    return spans.idle_ms(rec, ("train.data",))
