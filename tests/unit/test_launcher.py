"""Launcher: hostfile parsing, include/exclude filters, fan-out env contract,
local simulate mode, and ds_report (reference launcher/runner.py tests model:
tests/unit/launcher/test_run.py)."""
import os
import subprocess
import sys
import textwrap
from collections import OrderedDict

import pytest

from deepspeed_tpu.launcher import (decode_world_info, encode_world_info,
                                    fetch_hostfile, parse_resource_filter)
from deepspeed_tpu.launcher.runner import parse_args

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _hostfile(tmp_path, text):
    p = tmp_path / "hostfile"
    p.write_text(textwrap.dedent(text))
    return str(p)


def test_fetch_hostfile(tmp_path):
    path = _hostfile(tmp_path, """\
        # pod slice
        worker-0 slots=4
        worker-1 slots=4

        worker-2 slots=8   # big host
        """)
    pool = fetch_hostfile(path)
    assert pool == OrderedDict([("worker-0", 4), ("worker-1", 4), ("worker-2", 8)])


def test_fetch_hostfile_missing_returns_empty(tmp_path):
    assert fetch_hostfile(str(tmp_path / "nope")) == OrderedDict()


def test_fetch_hostfile_malformed_token_raises(tmp_path):
    path = _hostfile(tmp_path, "worker-0 slot=4\n")
    with pytest.raises(ValueError, match="unrecognized token"):
        fetch_hostfile(path)


def test_wait_all_or_fail_kills_hung_survivor():
    # proc 0 would block forever; proc 1 dies rc=3 -> survivor terminated,
    # failure propagated (regression: sequential wait loop hung here)
    from deepspeed_tpu.launcher.runner import wait_all_or_fail

    import time
    hang = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(600)"])
    boom = subprocess.Popen([sys.executable, "-c", "import sys; sys.exit(3)"])
    t0 = time.time()
    rc = wait_all_or_fail([hang, boom])
    assert rc == 3
    assert time.time() - t0 < 60
    assert hang.poll() is not None  # terminated, not orphaned


def test_fetch_hostfile_duplicate_raises(tmp_path):
    path = _hostfile(tmp_path, "h1 slots=2\nh1 slots=2\n")
    with pytest.raises(ValueError, match="duplicate"):
        fetch_hostfile(path)


POOL = OrderedDict([("w0", 4), ("w1", 4), ("w2", 4)])


def test_filter_none_selects_all():
    act = parse_resource_filter(POOL)
    assert act == OrderedDict([("w0", [0, 1, 2, 3]), ("w1", [0, 1, 2, 3]),
                               ("w2", [0, 1, 2, 3])])


def test_include_hosts():
    act = parse_resource_filter(POOL, include="w1@w2")
    assert list(act) == ["w1", "w2"]


def test_include_slots():
    act = parse_resource_filter(POOL, include="w0:0,2@w1:1-3")
    assert act == OrderedDict([("w0", [0, 2]), ("w1", [1, 2, 3])])


def test_exclude_whole_host_and_slots():
    act = parse_resource_filter(POOL, exclude="w1@w2:0-1")
    assert act == OrderedDict([("w0", [0, 1, 2, 3]), ("w2", [2, 3])])


def test_include_and_exclude_same_host_raises():
    with pytest.raises(ValueError, match="both"):
        parse_resource_filter(POOL, include="w0", exclude="w0:1")


def test_unknown_host_raises():
    with pytest.raises(ValueError, match="not in resource pool"):
        parse_resource_filter(POOL, include="nope")


def test_slot_out_of_range_raises():
    with pytest.raises(ValueError, match="out of range"):
        parse_resource_filter(POOL, include="w0:7")


def test_world_info_roundtrip():
    act = parse_resource_filter(POOL, exclude="w1")
    assert decode_world_info(encode_world_info(act)) == act


def test_parse_args_remainder():
    args = parse_args(["--num_nodes", "2", "train.py", "--lr", "3e-4"])
    assert args.user_script == "train.py"
    assert args.user_args == ["--lr", "3e-4"]
    assert args.num_nodes == 2


def test_fleet_flags_export_env_contract(tmp_path):
    """--fleet N exports the DS_TPU_FLEET_* contract to children (ISSUE 7:
    one binary, train or serve); it requires a coordination store and
    defaults its dir to --pod_coord_dir."""
    from deepspeed_tpu.launcher.runner import fleet_env

    args = parse_args(["--fleet", "3", "--fleet_coord_dir",
                       str(tmp_path / "coord"), "--fleet_lease", "2.5",
                       "serve.py"])
    env = fleet_env(args)
    assert env == {"DS_TPU_FLEET_SIZE": "3",
                   "DS_TPU_FLEET_COORD_DIR": str(tmp_path / "coord"),
                   "DS_TPU_FLEET_LEASE": "2.5",
                   "DS_TPU_FLEET_MISS_LIMIT": "3"}
    # defaults to the pod store when only that is given
    args = parse_args(["--fleet", "2", "--pod_coord_dir",
                       str(tmp_path / "pod"), "serve.py"])
    assert fleet_env(args)["DS_TPU_FLEET_COORD_DIR"] == str(tmp_path / "pod")
    # no fleet -> no exports; fleet without a store is an arg error
    assert fleet_env(parse_args(["train.py"])) == {}
    with pytest.raises(SystemExit):
        parse_args(["--fleet", "2", "serve.py"])


def test_ssh_runner_env_contract():
    from deepspeed_tpu.launcher.multinode_runner import SSHRunner

    args = parse_args(["train.py"])
    active = OrderedDict([("w0", [0, 1, 2, 3]), ("w1", [0, 3])])
    base = {"COORDINATOR_ADDRESS": "w0:8476", "NUM_PROCESSES": "2",
            "DS_TPU_WORLD_INFO": encode_world_info(active)}
    r = SSHRunner(args, active, base, pool={"w0": 4, "w1": 4})
    env0, env1 = r.env_for("w0"), r.env_for("w1")
    assert env0["PROCESS_ID"] == "0" and env1["PROCESS_ID"] == "1"
    # w0 keeps all 4 slots -> visibility untouched; w1 was narrowed -> pinned
    assert "TPU_VISIBLE_CHIPS" not in env0
    assert env1["TPU_VISIBLE_CHIPS"] == "0,3"
    cmd = r._ssh_cmd("w1", ["python", "train.py"])
    assert cmd[0] == "ssh" and "w1" in cmd
    assert "PROCESS_ID=1" in cmd[-1] and "python train.py" in cmd[-1]


def test_launcher_help_runs():
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher", "--help"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0
    assert "--hostfile" in out.stdout and "--include" in out.stdout


def test_launcher_single_host_local_exec(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text("import os; print('RAN', os.environ.get('COORDINATOR_ADDRESS'))\n")
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher", str(script)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "RAN None" in out.stdout


def test_launcher_simulate_two_procs(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(
        "import os\n"
        f"open(os.path.join({str(tmp_path)!r}, 'out.' + os.environ['PROCESS_ID']),"
        " 'w').write(os.environ['NUM_PROCESSES'])\n")
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher", "--simulate", "2",
         str(script)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    assert (tmp_path / "out.0").read_text() == "2"
    assert (tmp_path / "out.1").read_text() == "2"


def test_launcher_propagates_failure(tmp_path):
    script = tmp_path / "boom.py"
    script.write_text("import sys; sys.exit(3)\n")
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher", str(script)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 3


def test_ds_report_runs():
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.env_report"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "General environment" in out.stdout
    assert "Device inventory" in out.stdout


def test_ds_ssh_local_fallback(tmp_path, capsys):
    from deepspeed_tpu.launcher.ds_ssh import main

    rc = main(["-H", str(tmp_path / "missing_hostfile"), "--", "true"])
    assert rc == 0


def test_ds_ssh_hostfile_localhost(tmp_path):
    from deepspeed_tpu.launcher.ds_ssh import main

    hf = tmp_path / "hosts"
    hf.write_text("localhost slots=1\n")
    marker = tmp_path / "ran"
    rc = main(["-H", str(hf), "--", "touch", str(marker)])
    assert rc == 0 and marker.exists()


def test_comm_capability_probes():
    import deepspeed_tpu.comm as dist

    assert dist.has_all_gather_into_tensor() is True
    assert dist.has_reduce_scatter_tensor() is True
    assert dist.has_all_to_all_single() is True
    assert dist.has_coalescing_manager() is False


def test_ds_ssh_rejects_slot_filters(tmp_path, capsys):
    from deepspeed_tpu.launcher.ds_ssh import main

    hf = tmp_path / "hosts"
    hf.write_text("localhost slots=4\n")
    with pytest.raises(SystemExit):
        main(["-H", str(hf), "-e", "localhost:0-1", "--", "true"])


def test_ds_ssh_missing_command_rc(tmp_path):
    from deepspeed_tpu.launcher.ds_ssh import main

    hf = tmp_path / "hosts"
    hf.write_text("localhost slots=1\n")
    rc = main(["-H", str(hf), "--", "definitely_not_a_command_xyz"])
    assert rc == 127


def test_ds_ssh_completes_and_reports_nonzero(tmp_path, capsys):
    """Fleet semantics: the command runs to completion and the nonzero rc is
    reported, not turned into a SIGTERM of the fan-out."""
    from deepspeed_tpu.launcher.ds_ssh import main

    hf = tmp_path / "hosts"
    hf.write_text("localhost slots=1\n")
    m1 = tmp_path / "a"
    rc = main(["-H", str(hf), "--", "sh", "-c", f"touch {m1}; exit 3"])
    assert rc == 3 and m1.exists()
    assert "rc=3" in capsys.readouterr().err


def test_ds_ssh_missing_hostfile_with_filters_errors(tmp_path):
    from deepspeed_tpu.launcher.ds_ssh import main

    with pytest.raises(SystemExit):
        main(["-H", str(tmp_path / "nope"), "-e", "somehost", "--", "true"])
