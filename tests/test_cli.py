"""CLI subcommand tests (reference coverage model: ctl/*_test.go)."""

import threading

import pytest

from pilosa_tpu import cli, roaring
from pilosa_tpu.server import Server
from pilosa_tpu.utils.config import Config, config_template, dump_config, load_config


@pytest.fixture
def srv(tmp_path):
    s = Server(Config(bind="127.0.0.1:0", data_dir=str(tmp_path / "d"),
                      anti_entropy_interval=0))
    s.open()
    yield s
    s.close()


def test_cli_import_export_roundtrip(srv, tmp_path, capsys):
    csv = tmp_path / "data.csv"
    csv.write_text("1,10\n1,20\n2,10\n")
    host = f"127.0.0.1:{srv.port}"
    assert cli.main(["import", str(csv), "--host", host, "-i", "i", "-f", "f", "--create"]) == 0
    assert cli.main(["export", "--host", host, "-i", "i", "-f", "f"]) == 0
    out = capsys.readouterr().out
    assert "1,10" in out and "1,20" in out and "2,10" in out


def test_cli_import_values(srv, tmp_path, capsys):
    csv = tmp_path / "vals.csv"
    csv.write_text("10,5\n20,-3\n")
    host = f"127.0.0.1:{srv.port}"
    assert cli.main(["import", str(csv), "--host", host, "-i", "i", "-f", "v",
                     "--create", "--values"]) == 0
    assert srv.holder.index("i").field("v").value(10) == (5, True)
    assert srv.holder.index("i").field("v").value(20) == (-3, True)


def test_cli_check_and_inspect(tmp_path, capsys):
    import numpy as np

    good = tmp_path / "good"
    good.write_bytes(roaring.serialize(roaring.Bitmap.from_values(np.array([1, 2], dtype=np.uint64))))
    bad = tmp_path / "bad"
    bad.write_bytes(b"\x00\x01garbage")
    assert cli.main(["check", str(good)]) == 0
    assert cli.main(["check", str(good), str(bad)]) == 1
    out = capsys.readouterr().out
    assert "OK (2 bits" in out and "CORRUPT" in out
    assert cli.main(["inspect", str(good)]) == 0
    assert "bits: 2" in capsys.readouterr().out


def test_cli_config(tmp_path, capsys):
    assert cli.main(["config", "--generate"]) == 0
    template = capsys.readouterr().out
    assert 'bind = "127.0.0.1:10101"' in template
    cfg_file = tmp_path / "c.toml"
    cfg_file.write_text('bind = "0.0.0.0:9999"\nreplica-n = 3\n')
    assert cli.main(["config", "--config", str(cfg_file)]) == 0
    out = capsys.readouterr().out
    assert 'bind = "0.0.0.0:9999"' in out and "replica-n = 3" in out


def test_config_env_precedence(tmp_path):
    cfg_file = tmp_path / "c.toml"
    cfg_file.write_text('bind = "file:1"\ndata-dir = "/from-file"\n')
    cfg = load_config(
        str(cfg_file),
        env={"PILOSA_TPU_BIND": "env:2", "PILOSA_TPU_REPLICA_N": "5",
             "PILOSA_TPU_COORDINATOR": "true", "PILOSA_TPU_SEEDS": "a,b"},
        overrides={"bind": "flag:3"},
    )
    assert cfg.bind == "flag:3"        # flag wins
    assert cfg.data_dir == "/from-file"  # file when no env/flag
    assert cfg.replica_n == 5 and cfg.coordinator is True
    assert cfg.seeds == ["a", "b"]


def test_cli_explain_and_analyze(srv, tmp_path, capsys):
    csv = tmp_path / "ex.csv"
    csv.write_text("1,10\n1,20\n")
    host = f"127.0.0.1:{srv.port}"
    assert cli.main(["import", str(csv), "--host", host, "-i", "e",
                     "-f", "f", "--create"]) == 0
    capsys.readouterr()
    # plan only: the cost table renders with the chosen path marked
    assert cli.main(["explain", "Count(Row(f=1))", "--host", host,
                     "-i", "e"]) == 0
    out = capsys.readouterr().out
    assert "route mode:" in out and "host" in out and "device" in out
    assert "* " in out  # chosen-candidate marker
    assert "results:" not in out  # nothing executed
    # analyze: measured actuals + results
    assert cli.main(["explain", "Count(Row(f=1))", "--host", host,
                     "-i", "e", "--analyze"]) == 0
    out = capsys.readouterr().out
    assert "measured" in out and "error x" in out
    assert "results: [2]" in out
    # raw JSON passthrough
    assert cli.main(["explain", "Count(Row(f=1))", "--host", host,
                     "-i", "e", "--json"]) == 0
    import json as _json

    payload = _json.loads(capsys.readouterr().out)
    assert payload["explain"]["calls"][0]["call"] == "Count"
