"""mfu: the whole foreground step's share of the chips' peak bf16 FLOP/s
over the traced window: required FLOPs per token (``chipbench.flops``: 6 x
matmul parameters + causal attention; recomputation not counted) x tokens
trained per second of the traced window / (chips x peak), in %."""


def read(rec):
    red = rec["trace"]
    if red is None or rec["peak_flops"] is None:
        return None
    rate = red["steps"] * rec["tokens_per_step"] / red["window_s"]
    return 100 * rec["flops_per_token"] * rate / (rec["chips"] *
                                                  rec["peak_flops"])
