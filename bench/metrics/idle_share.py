"""Share of the traced stretch in which no program ran on the device, in
percent (one minus the union of module intervals over the stretch)."""
from benchkit import record


def read(run):
    return record.idle_share(run)
