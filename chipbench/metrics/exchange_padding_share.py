"""exchange_padding_share: the share of the all-to-all's slots that carried
no record, 100 x (slots - records) / slots, summed over the window's
map.sort spans, whose `exchange_slots` and `exchange_records` attributes
the program computes from its plan, its mesh width and the counts the sort
returns. None where the program records neither attribute."""


def read(run):
    slots = records = 0
    for job in run.jobs:
        for ev in job.events:
            if ev["name"] == "map.sort" and "exchange_slots" in ev:
                slots += ev["exchange_slots"]
                records += ev["exchange_records"]
    if not slots:
        return None
    return 100.0 * (slots - records) / slots
