"""From the device trace: share of the time inside ``process`` spans in
which no operation ran on the device, in percent."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("process_s"):
        return None
    return 100.0 * tr["process_idle_s"] / tr["process_s"]
