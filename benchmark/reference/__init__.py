"""The plain reference that decides `correct`: a frozen copy of the
scalar Automerge 0.x backend (`backend.py`, `op_set.py`,
`indexed_list.py`, `cow.py`), taken from the program's own scalar oracle
and cut loose from it.  It imports nothing of the system under test, so
no change to the program can move what the benchmark compares against.

    from reference import backend
    state, patch = backend.apply_changes(backend.init(), changes)
    backend.get_patch(state)
"""
