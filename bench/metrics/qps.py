"""Requests answered per second, for a closed loop: the requests sent in
the window and answered, over the seconds from the window's open to the
last of their answers (the callers move in rounds, so a count cut at
the close would jump by a round)."""


def read(obs):
    sent = [o for o in obs.outcomes if o.t_sent <= obs.t_close and o.answers is not None]
    if not sent:
        return None
    return len(sent) / (max(o.t_done for o in sent) - obs.t_open)
