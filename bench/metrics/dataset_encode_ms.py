"""Host ms of the program's own ``protocol.encode_dataset`` on the cell's
data, run eagerly as a training job's set-up runs it and ended by
``block_until_ready``: the mean of repeated calls spanning 250 ms or more."""


def read(m):
    s = m.host_spans_s.get("dataset_encode")
    return None if s is None else s * 1e3
