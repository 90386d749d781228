"""Attention kernels: the share of the block table the paged decode
kernel's walk covers, in percent. Over the window's ticks that made a
decode call, the block-table entries its rows walked
(TickRecord.kv_pages_walked: each row's entries up to the page of its
query position) over the whole table, max_slots x max_blocks, which a
walk that ignores what each slot holds covers in every call. Moves
tpot_p90_s."""

import lengths
from _window import ticks_in_window


def read(run):
    ticks = ticks_in_window(run)
    if not ticks or not hasattr(ticks[0], "kv_pages_walked"):
        return None
    walked = [t.kv_pages_walked for t in ticks if t.kv_pages_walked > 0]
    if not walked:
        return None
    page = run.config["serving"]["page_size"]
    max_blocks = -(-lengths.max_seq_len(run.traffic) // page)
    return 100.0 * sum(walked) / (len(walked) * run.max_slots * max_blocks)
