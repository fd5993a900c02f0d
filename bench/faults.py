"""Faults planted under the timed path, for the tests that see
``correct`` come out false (and for ``calibrate.py --fault``).  Each takes
an object with pytest's ``monkeypatch.setattr`` and the number of fields;
the cells run on one chip, so no exchange between chips exists to leave
out.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def unchanged(mp, fields):
    """Every sweep returns its state unchanged."""
    from repro.core import faults, sn_train

    mp.setattr(sn_train, "colored_sweep", lambda problem, state, *a, **k: state)
    mp.setattr(faults, "faulty_sweep", lambda problem, state, *a, **k: state)


def half_fields(mp, fields):
    """Half of the fields are left out of every sweep and every answer."""
    from repro.core import sn_train
    from repro.launch.daemon import Daemon

    sweep = sn_train.colored_sweep
    keep = jnp.arange(fields) < fields // 2

    def half(problem, state, *a, **k):
        out = sweep(problem, state, *a, **k)
        return sn_train.SNTrainState(
            z=jnp.where(keep[:, None], out.z, state.z),
            coef=jnp.where(keep[:, None, None], out.coef, state.coef),
        )

    pump = Daemon.pump

    def half_pump(self):
        out = []
        for a in pump(self):
            v = np.array(a.values)
            v[fields // 2:] = 0.0
            out.append(a._replace(values=v))
        return out

    mp.setattr(sn_train, "colored_sweep", half)
    mp.setattr(Daemon, "pump", half_pump)


def altered(mp, fields):
    """One value of every answer, and of every solve, is off by 0.05."""
    from repro.core import monitor
    from repro.launch.daemon import Daemon

    watch = monitor.watch_sweeps

    def altered_watch(*a, **k):
        problem, state, rc = watch(*a, **k)
        return problem, type(state)(z=state.z.at[0, 0].add(0.05), coef=state.coef), rc

    pump = Daemon.pump

    def altered_pump(self):
        out = []
        for a in pump(self):
            v = np.array(a.values)
            v[0, 0] += 0.05
            out.append(a._replace(values=v))
        return out

    mp.setattr(monitor, "watch_sweeps", altered_watch)
    mp.setattr(Daemon, "pump", altered_pump)


def early_stop(mp, fields):
    """The solver's convergence test is twice as loose as configured: it
    stops, and reports converged, early."""
    import dataclasses

    from repro.core import monitor

    watch = monitor.watch_sweeps

    def loose_watch(*a, config=monitor.WatchdogConfig(), **k):
        return watch(*a, config=dataclasses.replace(config, tol=2 * config.tol), **k)

    mp.setattr(monitor, "watch_sweeps", loose_watch)


FAULTS = {"unchanged": unchanged, "half_fields": half_fields, "altered": altered,
          "early_stop": early_stop}
# faults of the solve cells only: a serving cell's answers and ticks are
# compared at the sweep counts the program reports, whatever stopped them
SOLVE_ONLY = {"early_stop"}
