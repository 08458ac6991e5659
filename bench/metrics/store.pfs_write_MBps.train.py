"""PFS-tier bytes written over PFS write-busy time in the window (MB/s),
from the store's ``tier_stats()`` at the window's start and end."""


def read(rec):
    n, busy = rec.counters.get("pfs.bytes_written", 0), rec.counters.get("pfs.write_busy_s", 0)
    return n / 1e6 / busy if n > 0 and busy > 0 else None
