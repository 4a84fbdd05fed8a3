"""What the program cannot name of ``setup_s``: ``setup_s`` less the
traffic's ramp less the seconds under every ``setup.*`` span that no
other ``setup.*`` span encloses (``setup_spanned_seconds_total`` at the
window's opening). It holds the driver's own work (the check's
reference, the warm-up requests' device time beyond their first calls,
the client child). If set-up grows and no span does, it grew here.

The ramp is taken off whole, and so is a ``setup.*`` span that fired
inside it (a program the ramp's load was first to call): that span is
taken off twice and the value reads low by its seconds. A driver's
warm-up reaches every bucket before the ramp, so the five cells' watched
programs are all built by then; whether ``others`` builds anything
inside a ramp is not measured."""


def read(obs):
    from setup_reads import total
    named = total(obs, "setup_spanned_seconds_total")
    if named is None:
        return None
    ramp = float(obs["traffic"].get("ramp_s", 0.0))
    return obs["end_to_end"]["setup_s"] - ramp - named
