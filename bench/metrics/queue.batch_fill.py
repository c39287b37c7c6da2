"""Mean over served batches of the batch's size over the planned batch
size, in percent."""


def read(rec):
    fills = [b["size"] / b["planned"] for b in rec["batches"]]
    return 100.0 * sum(fills) / len(fills) if fills else None
