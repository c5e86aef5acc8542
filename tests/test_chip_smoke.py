"""chip_smoke.py's phases at tiny sizes on the CPU (the kernels run
interpreted): the same entry points and checks the chip run makes, so a
broken phase is found here and not on the chip.  ``main`` itself refuses
any platform but the TPU."""
import importlib.util
import json
import pathlib

import pytest

from conftest import run_subprocess_devices

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.fixture
def log():
    log = chip_smoke.CompileLog()
    yield log
    log.close()


@pytest.mark.parametrize("phase", ["bulk", "streamed", "service"])
def test_one_chip_phase_passes_on_cpu(phase, log, tmp_path):
    args = {"bulk": (chip_smoke.phase_bulk, 5000),
            "streamed": (chip_smoke.phase_streamed, 4000, 1024,
                         str(tmp_path)),
            "service": (chip_smoke.phase_service, 3000, str(tmp_path),
                        16384)}
    rec = chip_smoke.run_phase(phase, log, *args[phase])
    assert rec["ok"], rec
    assert rec["checks"] and all(rec["checks"].values())


def test_four_chip_phases_pass_on_fake_devices():
    out = run_subprocess_devices(4, f"""
import json, sys
sys.path.insert(0, {str(ROOT)!r})
import chip_smoke as cs
log = cs.CompileLog()
recs = [cs.run_phase('placement', log, cs.phase_placement, 4099, 4),
        cs.run_phase('row_sharded', log, cs.phase_row_sharded, 4099, 4),
        cs.run_phase('segment_batch', log, cs.phase_segment_batch, 600, 4)]
print(json.dumps({{r['phase']: r for r in recs}}, default=str))
""")
    for name, rec in out.items():
        assert rec["ok"], (name, rec)
    assert out["placement"]["shard_rows"] == [1032] * 4   # 4099 → 4128 rows
    assert out["segment_batch"]["bucket_rows"] == 1024


def test_run_phase_reports_compiles_and_failures(log):
    import jax
    import jax.numpy as jnp

    def compiles():
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
        return {"checks": {"ran": True}}

    def fails():
        raise ValueError("boom")

    good = chip_smoke.run_phase("good", log, compiles)
    bad = chip_smoke.run_phase("bad", log, fails)
    assert good["ok"] and good["compiles"] >= 1
    assert not bad["ok"] and "boom" in bad["error"]
    json.dumps(good, default=str)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_cpu(argv, capsys):
    assert chip_smoke.main(argv) == 1
    captured = capsys.readouterr()
    assert "needs a TPU, JAX found cpu" in captured.err
    assert '"ok"' not in captured.out
