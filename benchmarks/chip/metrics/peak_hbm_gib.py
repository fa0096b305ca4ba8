"""peak_hbm_gib: the device's peak memory (memory_stats'
peak_bytes_in_use plus peak_bytes_reserved, the programs' temporaries),
read when the drain ended and before the reference check, in GiB."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
