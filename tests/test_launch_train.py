"""``launch.train.train`` counts its steps on the host: the losses, the
logged steps and the checkpoint steps are those of a plain loop over the
same train step that reads the device's step after every call, a resume
continues them, and each step is a ``train`` step span in a profiler
trace."""
import glob
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch, reduced
from repro.data.pipeline import synthetic_batches
from repro.launch.train import train
from repro.training import train as TR

ARCH = "stablelm-1.6b"
SIZES = dict(batch=2, seq=16)


@pytest.fixture(scope="module")
def configs():
    spec = get_arch(ARCH)
    cfg = reduced(spec.model).replace(param_dtype="float32",
                                      compute_dtype="float32")
    tcfg = spec.train.__class__(optimizer=spec.train.optimizer,
                                learning_rate=1e-3, remat="none")
    return cfg, tcfg


def plain_loop(cfg, tcfg, steps, log_every, ckpt_every):
    """The loop as it was: the device's step read after every call."""
    state = TR.init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    step_fn = jax.jit(TR.make_train_step(cfg, tcfg))
    losses, logged, saved = [], [], []
    for b in itertools.islice(synthetic_batches(
            SIZES["batch"], SIZES["seq"], cfg.vocab_size, seed=0, n=steps),
            steps):
        state, m = step_fn(state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
        s = int(state["step"])
        if s % log_every == 0:
            logged.append(s)
        if s % ckpt_every == 0:
            saved.append(s)
    return losses, logged, saved


def logged_steps(out):
    return [int(l.split()[1]) for l in out.splitlines()
            if l.startswith("step ")]


def test_host_step_count_keeps_losses_logs_and_checkpoints(
        configs, tmp_path, capsys):
    cfg, tcfg = configs
    want, want_logged, want_saved = plain_loop(cfg, tcfg, 9, 2, 3)
    ckpt = str(tmp_path / "ckpt")
    kw = dict(arch=ARCH, ckpt_dir=ckpt, ckpt_every=3, log_every=2, **SIZES)
    first = train(cfg, tcfg, steps=6, **kw)
    out = capsys.readouterr().out
    np.testing.assert_allclose(first, want[:6], rtol=1e-6)
    assert logged_steps(out) == [s for s in want_logged if s <= 6]
    resumed = train(cfg, tcfg, steps=9, **kw)        # from the step-6 save
    out = capsys.readouterr().out
    assert "resuming from checkpoint step 6" in out
    np.testing.assert_allclose(resumed, want[6:], rtol=1e-6)
    assert logged_steps(out) == [s for s in want_logged if s > 6]
    saved = sorted(int(os.path.basename(d).split("_")[1])
                   for d in glob.glob(os.path.join(ckpt, "step_*")))
    assert saved == want_saved


def test_each_step_is_a_train_step_span(configs, tmp_path):
    cfg, tcfg = configs
    train(cfg, tcfg, arch=ARCH, steps=1, **SIZES)   # compiles untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        train(cfg, tcfg, arch=ARCH, steps=3, **SIZES)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [e for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU" for line in p.lines
             for e in line.events if e.name == "train"]
    assert len(spans) == 3
