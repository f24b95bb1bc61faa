"""exchange_fill.records: bytes of real records over the bytes each chip
put on the wire in the all-to-all (its padded send buffer to the other
chips), in percent, over the window's calls: the program's counters
`repro.runtime.spans.SENT_BYTES` and `WIRE_BYTES`. The dense exchange
pads each pair's run to `pair_factor` times its share, so about 100 /
pair_factor is what balanced splitters give. A program without the
counters reads nothing."""


def read(run):
    wire = sum(run.data.get("wire_bytes") or ())
    if wire <= 0:
        return None
    return 100.0 * sum(run.data["sent_bytes"]) / wire
