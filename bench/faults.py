"""Faults planted in the timed path, to show that each cell's comparison
catches them: a whole run with one of these applied must come out not
correct.  Each takes ``patch(owner, name, value)`` (``setattr`` that the
caller undoes, such as pytest's ``monkeypatch.setattr``).

``calibrate.py --fault <name>`` reads them on the chip at a cell's own
size; ``tests/test_bench_faults.py`` drives them at a test size.
"""
import jax.numpy as jnp


def flip_label(patch):
    from repro.core.cluster_engine import ClusterEngine

    orig = ClusterEngine.cut

    def cut(self, dend, n_clusters):
        labels = orig(self, dend, n_clusters)
        return labels.at[0].set((labels[0] + 1) % n_clusters)

    patch(ClusterEngine, "cut", cut)


def half_rows(patch):
    from repro.core.signature_engine import SignatureEngine

    orig = SignatureEngine._accumulate_grams

    def half(self, raw, nv, assume_full=False):
        h = raw.shape[1] // 2
        return orig(self, raw[:, :h], jnp.minimum(nv, h), assume_full)

    patch(SignatureEngine, "_accumulate_grams", half)


def flip_newcomer(patch):
    from repro.core import membership_engine as me

    orig = me.MembershipEngine.assign

    def assign(self, lam, v):
        res = orig(self, lam, v)
        t = self.state.n_clusters
        return me.AssignResult(
            labels=res.labels.at[0].set((res.labels[0] + 1) % t),
            affinity=res.affinity, margin=res.margin)

    patch(me.MembershipEngine, "assign", assign)


def frozen_directory(patch):
    from repro.core import membership_engine as me

    patch(me, "_proto_update",
          lambda protos, counts, delta, m, sign: (protos, counts))


def half_wave(patch):
    from repro.core import membership_engine as me

    orig = me._wave_outer_sums

    def half(v_wave, labels, n_clusters_arr):
        h = v_wave.shape[0] // 2
        return orig(v_wave[:h], labels[:h], n_clusters_arr)

    patch(me, "_wave_outer_sums", half)


def frozen_round(patch):
    from repro.fed import trainer

    orig = trainer._fused_global_round

    def frozen(p_stack, *args, **kw):
        _, loss = orig(p_stack, *args, **kw)
        return p_stack, loss

    patch(trainer, "_fused_global_round", frozen)


def frozen_leaf(patch):
    """One small leaf (the second conv's bias) left where it was, every
    other leaf updated."""
    from repro.fed import trainer

    orig = trainer._fused_global_round

    def frozen(p_stack, *args, **kw):
        new, loss = orig(p_stack, *args, **kw)
        new = dict(new, conv2=dict(new["conv2"], b=p_stack["conv2"]["b"]))
        return new, loss

    patch(trainer, "_fused_global_round", frozen)


def half_batch(patch):
    from repro.models import cnn

    orig = cnn.loss_fn

    def loss_fn(cfg):
        f = orig(cfg)

        def half(params, batch):
            h = batch["y"].shape[0] // 2
            return f(params, {"x": batch["x"][:h], "y": batch["y"][:h]})

        return half

    patch(cnn, "loss_fn", loss_fn)


def no_gps_exchange(patch):
    from repro.fed import hierarchy

    patch(hierarchy, "gps_aggregate_stacked",
          lambda stack, w, is_common, axis=None: stack)


#: the faults each traffic driver's cells can have
BY_DRIVER = {
    "oneshot": [flip_label, half_rows],
    "arrivals": [flip_newcomer, frozen_directory, half_wave],
    "mthfl": [frozen_round, frozen_leaf, half_batch, no_gps_exchange],
}
