"""Peak device memory of the fullest chip since the process started, in
GB (1e9 bytes): ``peak_bytes_reserved`` (the programs' temporaries) plus
``peak_bytes_in_use`` (live arrays) of ``memory_stats()``; see
``instruments.memory_peak_bytes``."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 1e9
