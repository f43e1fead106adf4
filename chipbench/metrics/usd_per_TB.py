"""usd_per_TB: what the window cost per TB sorted, the CloudSort ranking
metric. Chip-hours at the chip's price for the window's seconds, plus the
durable tier's GETs and PUTs at S3 request prices (harness/tables.py); spill
requests are free, as on local NVMe. None off the chip (no price)."""


def read(run):
    if run.prices is None:
        return None
    p = run.prices
    usd = (p["chip_hour"] * run.chips * run.window_s / 3600.0
           + p["get_per_1000"] * run.durable_gets / 1000.0
           + p["put_per_1000"] * run.durable_puts / 1000.0)
    return usd / (run.input_bytes / 1e12)
