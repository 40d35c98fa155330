"""Peak bytes in use on the fullest device (``/status``)."""
LAYER = "device"
UNIT = "MiB"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_counter"


def read(ctx):
    peaks = [
        d.get("peak_bytes_in_use") or 0
        for d in ctx["status"]["after"].get("devices", [])
    ]
    return max(peaks) / (1 << 20) if peaks and max(peaks) else None
