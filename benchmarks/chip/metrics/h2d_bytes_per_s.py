"""Host-to-device bytes per second: the program's ``transfer.bytes`` over
the summed self time of its ``scan.transfer`` spans, which end when the
copy has landed on the device."""
from program_spans import counter, recorders, self_seconds


def read(run):
    recs = recorders(run)
    seconds = self_seconds(recs, "scan.transfer")
    if seconds <= 0:
        return None
    return counter(recs, "transfer.bytes") / seconds
