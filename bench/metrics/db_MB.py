"""Bytes of ``db.pms`` and ``db.cms`` of one analysis, in MB (10^6 bytes),
as the file system reports them after the window."""


def read(run):
    return run.db_bytes / 1e6 if run.db_bytes else None
