"""maintain_share.ingest: maintenance, host time in ``maintain`` as a
share of the traced part's wall clock, in %."""
from bench.readers import maintain_share


def read(run):
    return maintain_share(run)
