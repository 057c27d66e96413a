"""Requests whose answers were ready on the device inside the window,
divided by the window's length (a closed loop's window closes on a batch's
answers, ``harness.Driver.drive``)."""


def read(run):
    done = sum(1 for r in run.reqs
               if r.done_s is not None and not r.failed and r.done_s <= run.window_s)
    return done / run.window_s
