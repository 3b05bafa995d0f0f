"""Seconds of set-up the program spends building its state: its
``startup.state`` records (``Trainer.__init__``, ``ServeEngine.__init__``).
The ``#`` line gives the phases under each (records whose ``parent`` is
a state's ``t0``) by name."""

from benchmarks.layer_metrics import _setup_common as su

NAME = "setup_state_s"
UNIT, LAYER, MOVES, SOURCE = su.UNIT, su.LAYER, su.MOVES, su.SOURCE


def read(run):
    states = su.named(run, "startup.state")
    starts = {e[1] for e in states}
    children: dict = {}
    for e in su.kept(run):
        if e[3] in starts:
            children[e[0]] = children.get(e[0], 0.0) + e[2]
    return su.say(NAME, su.union_s(states), {
        "kinds": [str(e[4][0]) for e in states if e[4]],
        "children_s": children,
    })
