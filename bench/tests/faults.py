"""Faults planted in the program's timed path, for the harness's tests: each
wraps the step factory it replaces in ``repro.core.hfl``."""
import jax


def unchanged(make):
    """A train step that returns its state unchanged but for the count."""
    def build(loss_fn, opt, sched):
        step = make(loss_fn, opt, sched)

        def broken(state, batch):
            _, losses = step(state, batch)
            return state._replace(step=state.step + 1), losses
        return broken
    return build


def half_batch(make):
    """A train step that sees the first half of each cluster's rows."""
    def build(loss_fn, opt, sched):
        step = make(loss_fn, opt, sched)

        def broken(state, batch):
            half = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batch)
            return step(state, half)
        return broken
    return build


def answer_altered(make):
    """A sync whose new reference has its embedding scaled by 1.01."""
    def build(plan):
        sync = make(plan)

        def broken(state):
            new = sync(state)
            w = dict(new.w_ref)
            w["embed"] = w["embed"] * 1.01
            return new._replace(w_ref=w)
        return broken
    return build


# (factory in repro.core.hfl, fault) by the fault's name
PLANTED = {
    "state_unchanged": ("make_cluster_train_step", unchanged),
    "half_batch": ("make_cluster_train_step", half_batch),
    "answer_altered": ("make_sync", answer_altered),
}
