"""Share of the chip's bf16 peak in fixed model work per train step
(``metrics_lib.train_flops``) times steps over the traced window, in
percent."""

from chipbench.metrics_lib import peak_flops, train_flops


def read(run):
    c = run.counters
    if not c.get("steps") or run.window_s <= 0:
        return None
    flops = train_flops(run.spec, run.mix["batch"], run.mix["seq"])
    return 100.0 * flops * c["steps"] / run.window_s / peak_flops(run)
