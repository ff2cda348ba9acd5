"""Loop `closed`: a session sends its next request when the last is
answered, until the window closes, and finishes the request it has in
flight.  The traffic file gives `sessions`; nothing else is read."""

import time


def session(sessions, k: int, out: list, win, shape: dict) -> None:
    while time.perf_counter() < win.t1 and not win.stop:
        sessions.send_one(k, out)
        if out[-1].reply is None:
            return
