"""Share (%) of the window that request submission spent waiting for
the SLO layer's lock, which each scheduling round holds: the program's
``submit_lock_wait_s`` read at the window's edges."""


def read(record):
    lock = record.get("submit_lock")
    if lock is None or record["seconds"] <= 0:
        return None
    waited = lock[1]["submit_lock_wait_s"] - lock[0]["submit_lock_wait_s"]
    return 100.0 * waited / record["seconds"]
