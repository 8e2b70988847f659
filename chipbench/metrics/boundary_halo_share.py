"""boundary_halo_share (collectives, trace): device self time of the work
that exists only because the matrix is split over chips, over the device's
busy time in the window, in %, mean over the devices whose ops the profile
names (``scopes.named_devices``).  Read by the program's scopes:
``DistEll.boundary`` (rows that border another slab), ``DistEll.halo``
(their remote columns) and ``DistEll.halo_exchange`` (the ``all_gather``
and the gather of the halo entries).  ``None`` where no op ran under
them."""

from chipbench import scopes

SCOPES = ("DistEll.boundary", "DistEll.halo", "DistEll.halo_exchange")

scopes.enable_for_traced_run()


def read(ctx):
    if ctx.summary is None or not ctx.lib.distributed:
        return None
    seconds = scopes.seconds_under(ctx, *SCOPES)
    if seconds is None:
        return None
    devices = scopes.named_devices(scopes.load(ctx))
    busy = sum(ctx.summary.busy_s[d] for d in devices) / len(devices)
    return 100.0 * seconds / busy
