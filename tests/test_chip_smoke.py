"""chip_smoke.py must not be able to succeed without the device: on the
CPU, and alone in a directory, it exits non-zero and prints no result.
Its legs also run here at a tiny size with the device checks off, so the
script does not rot between chip runs."""
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY = dict(vocab=512, layers=2, heads=2, max_length=128)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_the_cpu_and_names_it():
    r = _run(REPO)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr, r.stderr[-2000:]
    assert '"platform":"cpu"' in r.stdout      # states what it found, first
    assert '"ok"' not in r.stdout              # and prints no result


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run(str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_train_leg_tiny_on_cpu(capsys):
    """Same code as the chip run (trainer build, precompile, stepped loss +
    finite flag, both sync windows) over the 8 virtual devices."""
    chip_smoke.train_leg(dict(chip_smoke.TRAIN, **TINY, units=128,
                              hidden=256, batch=8, seq=128, max_pred=8,
                              steps=2, sync_steps=1), on_chip=False)
    out = capsys.readouterr().out
    assert '"mesh":{"data":8}' in out and '"step":2' in out
    assert '"sync_rule"' in out


def test_serve_leg_tiny_on_cpu(capsys):
    """Same code as the chip run: engine -> ModelServer -> client over
    HTTP, two prefill buckets, zero compilations after warm-up, logits
    parity through the ring cache."""
    chip_smoke.serve_leg(dict(chip_smoke.SERVE, **TINY, units=64, hidden=128,
                              slots=4, max_len=128, new_tokens=8),
                         on_chip=False)
    out = capsys.readouterr().out
    assert '"requests_answered":6' in out
    assert '"compilations_after_warmup":0' in out
    assert '"logits_max_abs_diff"' in out
