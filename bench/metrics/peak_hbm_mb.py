"""Peak device memory of the run, in MB (1e6 bytes): the fullest chip's
`peak_bytes_in_use` after the window."""


def read(ctx):
    b = ctx.get("memory_peak_bytes")
    return b / 1e6 if b else None
