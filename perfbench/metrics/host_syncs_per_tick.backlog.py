"""Device-to-host readbacks the service counts per tick over the window
(``RecoveryService.sync_log``)."""


def read(run):
    syncs = run.rec.host_syncs
    return sum(syncs) / len(syncs) if syncs else None
