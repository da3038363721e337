"""Per engine `step()` call: its host span minus the device-busy time
inside it, averaged over the traced steps (admission, token and position
build, the argmax pull)."""
from benchkit import record


def read(run):
    return record.engine_host_ms(run)
