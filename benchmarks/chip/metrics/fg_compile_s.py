"""fg_compile_s: seconds ``train()`` spent compiling (or loading from the
persistent cache) the foreground step, ``TrainReport.compile_s``."""


def read(rec):
    return rec["compile_s"]
