"""CLI / app-commands / export-import / dashboard / admin tests
(mirrors reference console behavior + AdminAPISpec)."""

import json
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.tools import app_commands as ac
from predictionio_tpu.tools.cli import main as cli_main


def call(port, method, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            ct = resp.headers.get("Content-Type", "")
            data = resp.read()
            return resp.status, (json.loads(data) if "json" in ct
                                 else data.decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


class TestAppCommands:
    def test_app_lifecycle(self, tmp_env):
        desc = ac.app_new("app1", description="my app")
        assert desc.app.name == "app1"
        assert len(desc.access_keys) == 1 and desc.access_keys[0].key
        with pytest.raises(ac.AppCommandError):
            ac.app_new("app1")
        assert [d.app.name for d in ac.app_list()] == ["app1"]
        shown = ac.app_show("app1")
        assert shown.app.description == "my app"
        ac.app_delete("app1")
        assert ac.app_list() == []
        with pytest.raises(ac.AppCommandError):
            ac.app_show("app1")

    def test_channels(self, tmp_env):
        ac.app_new("app2")
        c = ac.channel_new("app2", "chan-x")
        assert c.id > 0
        with pytest.raises(ac.AppCommandError):
            ac.channel_new("app2", "chan-x")
        with pytest.raises(ac.AppCommandError):
            ac.channel_new("app2", "bad name!")
        assert [ch.name for ch in ac.app_show("app2").channels] == ["chan-x"]
        ac.channel_delete("app2", "chan-x")
        assert ac.app_show("app2").channels == []

    def test_data_delete(self, tmp_env):
        desc = ac.app_new("app3")
        ev = Storage.get_events()
        ev.insert(Event(event="rate", entity_type="u", entity_id="1"),
                  desc.app.id)
        assert len(list(ev.find(desc.app.id))) == 1
        ac.app_data_delete("app3")
        assert list(ev.find(desc.app.id)) == []

    def test_accesskeys(self, tmp_env):
        ac.app_new("app4")
        k = ac.accesskey_new("app4", events=["rate"])
        assert k.events == ("rate",)
        keys = ac.accesskey_list("app4")
        assert len(keys) == 2  # default + new
        ac.accesskey_delete(k.key)
        assert len(ac.accesskey_list("app4")) == 1


class TestExportImport:
    def test_round_trip(self, tmp_env, tmp_path):
        desc = ac.app_new("exapp")
        ev = Storage.get_events()
        for i in range(25):
            ev.insert(Event(event="rate", entity_type="user",
                            entity_id=f"u{i}", target_entity_type="item",
                            target_entity_id=f"i{i}",
                            properties=DataMap({"rating": float(i)})),
                      desc.app.id)
        out = tmp_path / "events.jsonl"
        from predictionio_tpu.tools.export_import import (export_events,
                                                          import_events)
        assert export_events(desc.app.id, str(out)) == 25
        assert len(out.read_text().splitlines()) == 25

        desc2 = ac.app_new("imapp")
        assert import_events(desc2.app.id, str(out)) == 25
        got = sorted(e.entity_id for e in ev.find(desc2.app.id))
        assert len(got) == 25
        e0 = next(iter(ev.find(desc2.app.id, entity_id="u3",
                               entity_type="user")))
        assert e0.properties.get("rating", float) == 3.0


class TestParquetExportImport:
    def test_parquet_round_trip(self, tmp_env, tmp_path):
        """pio export --format parquet -> pio import --format parquet
        preserves every event field including free-form properties,
        tags, and timezone-aware times (the reference's DEFAULT export
        format, EventsToFile.scala:35)."""
        import datetime as dt
        desc = ac.app_new("pqapp")
        ev = Storage.get_events()
        t0 = dt.datetime(2026, 3, 1, 12, 30, 45, 123000,
                         tzinfo=dt.timezone.utc)
        for i in range(7):
            ev.insert(Event(event="rate", entity_type="user",
                            entity_id=f"u{i}", target_entity_type="item",
                            target_entity_id=f"i{i}",
                            properties=DataMap({"rating": float(i),
                                                "nested": {"a": [1, i]}}),
                            tags=("t1", f"t{i}"),
                            event_time=t0 + dt.timedelta(seconds=i)),
                      desc.app.id)
        ev.insert(Event(event="$set", entity_type="user",
                        entity_id="bare"), desc.app.id)  # minimal event

        out = tmp_path / "events.parquet"
        from predictionio_tpu.tools.cli import main as cli_main
        assert cli_main(["export", "--appid", str(desc.app.id),
                         "--output", str(out),
                         "--format", "parquet"]) == 0

        desc2 = ac.app_new("pqapp2")
        assert cli_main(["import", "--appid", str(desc2.app.id),
                         "--input", str(out),
                         "--format", "parquet"]) == 0
        got = {e.entity_id: e for e in ev.find(desc2.app.id)}
        assert len(got) == 8
        e3 = got["u3"]
        assert e3.properties.get("rating", float) == 3.0
        assert e3.properties["nested"] == {"a": [1, 3]}
        assert set(e3.tags) == {"t1", "t3"}
        assert e3.event_time == t0 + dt.timedelta(seconds=3)
        assert e3.event_time.tzinfo is not None
        assert got["bare"].event == "$set"
        assert got["bare"].target_entity_id is None

    def test_foreign_parquet_is_validated(self, tmp_env, tmp_path):
        """A hand-built parquet file gets the same scrutiny as JSON
        import: reserved/invalid names rejected, null required fields
        rejected — nothing lands in the store unvalidated."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        from predictionio_tpu.tools.export_import import (
            _parquet_schema, parquet_events)

        def write(path, event, entity_type, entity_id):
            pq.write_table(pa.table({
                "eventId": [None], "event": [event],
                "entityType": [entity_type], "entityId": [entity_id],
                "targetEntityType": [None], "targetEntityId": [None],
                "properties": ["{}"], "eventTime": [None],
                "tags": [[]], "prId": [None], "creationTime": [None],
            }, schema=_parquet_schema()), path)

        bad_name = tmp_path / "badname.parquet"
        write(bad_name, "$bogus", "user", "u1")
        with pytest.raises(Exception, match=r"\$bogus|reserved|invalid"):
            list(parquet_events(str(bad_name)))

        null_req = tmp_path / "nullreq.parquet"
        write(null_req, "rate", None, "u1")
        with pytest.raises(ValueError, match="entityType"):
            list(parquet_events(str(null_req)))

        ok = tmp_path / "ok.parquet"
        write(ok, "rate", "user", "u1")
        evs = list(parquet_events(str(ok)))
        assert len(evs) == 1
        assert evs[0].event_time is not None  # defaulted, not None


class TestMovieLensImport:
    """`pio import --format movielens` consumes the real dataset files
    (ML-100K u.data TSV, ML-20M ratings.csv, dirs, .zip archives) with
    no network assumption."""

    ML100K = "196\t242\t3.0\t881250949\n186\t302\t3.0\t891717742\n"
    ML20M = ("userId,movieId,rating,timestamp\n"
             "1,2,3.5,1112486027\n1,29,3.5,1112484676\n2,2,4.0,974820598\n")

    def _import(self, path):
        from predictionio_tpu.tools.export_import import import_movielens
        desc = ac.app_new(f"ml_{abs(hash(str(path))) % 10_000}")
        n = import_movielens(desc.app.id, str(path))
        return desc.app.id, n

    def test_ml100k_tsv(self, tmp_env, tmp_path):
        p = tmp_path / "u.data"
        p.write_text(self.ML100K)
        app_id, n = self._import(p)
        assert n == 2
        ev = Storage.get_events()
        e = next(iter(ev.find(app_id, entity_id="196",
                              entity_type="user")))
        assert e.event == "rate"
        assert e.target_entity_id == "242"
        assert e.properties.get("rating", float) == 3.0
        assert e.event_time.year == 1997  # real ML-100K epoch seconds

    def test_ml20m_csv_and_directory(self, tmp_env, tmp_path):
        d = tmp_path / "ml-20m"
        d.mkdir()
        (d / "ratings.csv").write_text(self.ML20M)
        app_id, n = self._import(d)  # directory form
        assert n == 3
        ev = Storage.get_events()
        got = {(e.entity_id, e.target_entity_id)
               for e in ev.find(app_id)}
        assert ("2", "2") in got and len(got) == 3

    def test_zip_archive(self, tmp_env, tmp_path):
        import zipfile
        z = tmp_path / "ml-20m.zip"
        with zipfile.ZipFile(z, "w") as zf:
            zf.writestr("ml-20m/ratings.csv", self.ML20M)
        app_id, n = self._import(z)
        assert n == 3

    def test_rejects_unknown_csv_header(self, tmp_env, tmp_path):
        p = tmp_path / "ratings.csv"
        p.write_text("foo,bar\n1,2\n")
        from predictionio_tpu.tools.export_import import movielens_events
        with pytest.raises(ValueError, match="header"):
            list(movielens_events(str(p)))

    def test_cli_import_format_flag(self, tmp_env, tmp_path, capsys):
        """`pio import --format movielens` end to end through argparse
        (the wiring the quickstart docs promise)."""
        from predictionio_tpu.tools.cli import main as cli_main
        p = tmp_path / "u.data"
        p.write_text(self.ML100K)
        desc = ac.app_new("mlcli")
        rc = cli_main(["import", "--appid", str(desc.app.id),
                       "--input", str(p), "--format", "movielens"])
        assert rc == 0
        assert "Imported 2 events." in capsys.readouterr().out
        ev = Storage.get_events()
        assert len(list(ev.find(desc.app.id))) == 2

    def test_feeds_the_recommendation_datasource(self, tmp_env, tmp_path):
        """End of the promised chain: imported real-format data is
        trainable by the recommendation template as-is."""
        from predictionio_tpu.models import recommendation as R
        p = tmp_path / "u.data"
        rows = "".join(f"{u}\t{i}\t{(u * i) % 5 + 1}.0\t88125094{u}\n"
                       for u in range(1, 5) for i in range(1, 6))
        p.write_text(rows)
        desc = ac.app_new("mltrain")
        from predictionio_tpu.tools.export_import import import_movielens
        assert import_movielens(desc.app.id, str(p)) == 20
        ds = R.RecommendationDataSource(
            R.DataSourceParams(app_name="mltrain"))
        td = ds.read_training()
        pd = R.RecommendationPreparator().prepare(td)
        assert pd.ratings_coo.nnz == 20


class TestTrim:
    def test_trim_window_into_fresh_app(self, tmp_env, capsys):
        """pio trim copies only the [start, until) window and refuses a
        non-empty destination (the reference trim-app contract)."""
        import datetime as dt
        UTC = dt.timezone.utc
        src = ac.app_new("trimsrc")
        ev = Storage.get_events()
        for i in range(10):
            ev.insert(Event(event="rate", entity_type="user",
                            entity_id=f"u{i}",
                            event_time=dt.datetime(2026, 1, 1, 0, 0, i,
                                                   tzinfo=UTC)),
                      src.app.id)
        dst = ac.app_new("trimdst")
        assert cli_main(["trim", "--src-appid", str(src.app.id),
                         "--dst-appid", str(dst.app.id),
                         "--start", "2026-01-01T00:00:03.000Z",
                         "--until", "2026-01-01T00:00:07.000Z"]) == 0
        assert "Trimmed 4 events" in capsys.readouterr().out
        got = sorted(e.entity_id for e in ev.find(dst.app.id))
        assert got == ["u3", "u4", "u5", "u6"]
        # destination now non-empty: a second trim refuses
        assert cli_main(["trim", "--src-appid", str(src.app.id),
                         "--dst-appid", str(dst.app.id)]) == 1
        assert "not empty" in capsys.readouterr().out
        # unregistered apps fail fast
        assert cli_main(["trim", "--src-appid", str(src.app.id),
                         "--dst-appid", "99"]) == 1
        assert "does not exist" in capsys.readouterr().out
        # dirt hiding in a NON-default channel still counts as non-empty
        dst2 = ac.app_new("trimdst2")
        ch = ac.channel_new("trimdst2", "side")
        ev.insert(Event(event="buy", entity_type="user", entity_id="x"),
                  dst2.app.id, ch.id)
        assert cli_main(["trim", "--src-appid", str(src.app.id),
                         "--dst-appid", str(dst2.app.id)]) == 1
        assert "not empty" in capsys.readouterr().out


class TestCLI:
    def test_version_status_build(self, tmp_env, tmp_path, capsys):
        assert cli_main(["version"]) == 0
        assert cli_main(["status"]) == 0
        out = capsys.readouterr().out
        assert "METADATA: OK" in out
        variant = {"engineFactory": "recommendation",
                   "datasource": {"params": {"app_name": "x"}},
                   "algorithms": [{"name": "als", "params": {"rank": 5}}]}
        vf = tmp_path / "engine.json"
        vf.write_text(json.dumps(variant))
        assert cli_main(["build", "--engine-json", str(vf)]) == 0
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"engineFactory": "nope"}))
        with pytest.raises(KeyError):
            cli_main(["build", "--engine-json", str(bad)])

    def test_app_cli(self, tmp_env, capsys):
        assert cli_main(["app", "new", "cliapp", "--access-key", "k1"]) == 0
        out = capsys.readouterr().out
        assert "cliapp" in out and "k1" in out
        assert cli_main(["app", "list"]) == 0
        assert cli_main(["app", "channel-new", "cliapp", "ch1"]) == 0
        assert cli_main(["accesskey", "new", "cliapp"]) == 0
        assert cli_main(["accesskey", "list", "cliapp"]) == 0
        assert cli_main(["app", "delete", "cliapp", "-f"]) == 0
        assert cli_main(["app", "show", "cliapp"]) == 1

    def test_template_cli(self, tmp_env, tmp_path, capsys):
        assert cli_main(["template", "list"]) == 0
        out = capsys.readouterr().out
        assert "recommendation" in out
        tdir = tmp_path / "eng"
        assert cli_main(["template", "get", "recommendation",
                         str(tdir)]) == 0
        variant = json.loads((tdir / "engine.json").read_text())
        assert variant["engineFactory"] == "recommendation"
        assert (tdir / "README.md").exists()
        assert cli_main(["template", "get", "nope", str(tdir)]) == 1

    @staticmethod
    def _make_gallery(root, archives):
        """Build a file:// gallery: index.json + per-template tar.gz."""
        import io
        import tarfile
        root.mkdir(parents=True, exist_ok=True)
        entries = []
        for name, files in archives.items():
            buf = io.BytesIO()
            with tarfile.open(fileobj=buf, mode="w:gz") as tf:
                for fname, content in files:
                    data = content.encode()
                    ti = tarfile.TarInfo(fname)
                    ti.size = len(data)
                    tf.addfile(ti, io.BytesIO(data))
            (root / f"{name}.tar.gz").write_bytes(buf.getvalue())
            entries.append({"name": name, "description": f"{name} desc",
                            "archive": f"{name}.tar.gz"})
        (root / "index.json").write_text(
            json.dumps({"templates": entries}))

    def test_gallery_index_list_and_get(self, tmp_env, tmp_path, capsys):
        """The remote-index mechanism of the reference's template tool
        (Template.scala:130-416): list merges the URI index, get fetches
        and extracts the archive through the scheme adapter."""
        g = tmp_path / "gallery"
        self._make_gallery(g, {"custom-engine": [
            ("engine.json", '{"engineFactory": "recommendation"}'),
            ("src/main.py", "print('hi')\n")]})
        uri = f"file://{g}"
        assert cli_main(["template", "list", "--gallery", uri]) == 0
        out = capsys.readouterr().out
        assert "custom-engine" in out and "recommendation" in out
        tdir = tmp_path / "eng2"
        assert cli_main(["template", "get", "custom-engine", str(tdir),
                         "--gallery", uri]) == 0
        assert json.loads((tdir / "engine.json").read_text())[
            "engineFactory"] == "recommendation"
        assert (tdir / "src" / "main.py").read_text() == "print('hi')\n"
        # built-ins still resolve when absent from the gallery
        tdir3 = tmp_path / "eng3"
        assert cli_main(["template", "get", "recommendation", str(tdir3),
                         "--gallery", uri]) == 0
        # env-var configuration path
        import os
        os.environ["PIO_TEMPLATE_GALLERY"] = uri
        try:
            assert cli_main(["template", "list"]) == 0
            assert "custom-engine" in capsys.readouterr().out
        finally:
            del os.environ["PIO_TEMPLATE_GALLERY"]

    def test_gallery_rejects_traversal_and_links(self, tmp_env, tmp_path):
        """Archive members escaping the target dir (or links) must be
        refused — the index is remote content."""
        import io
        import tarfile
        g = tmp_path / "gallery"
        g.mkdir()
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tf:
            data = b"evil"
            ti = tarfile.TarInfo("../evil.txt")
            ti.size = len(data)
            tf.addfile(ti, io.BytesIO(data))
        (g / "bad.tar.gz").write_bytes(buf.getvalue())
        (g / "index.json").write_text(json.dumps({"templates": [
            {"name": "bad", "archive": "bad.tar.gz"}]}))
        tdir = tmp_path / "out"
        assert cli_main(["template", "get", "bad", str(tdir),
                         "--gallery", f"file://{g}"]) == 1
        assert not (tmp_path / "evil.txt").exists()
        # symlink member
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tf:
            ti = tarfile.TarInfo("link")
            ti.type = tarfile.SYMTYPE
            ti.linkname = "/etc/passwd"
            tf.addfile(ti)
        (g / "bad.tar.gz").write_bytes(buf.getvalue())
        assert cli_main(["template", "get", "bad", str(tdir),
                         "--gallery", f"file://{g}"]) == 1

    def test_gallery_missing_index_fails_cleanly(self, tmp_env, tmp_path):
        assert cli_main(["template", "list", "--gallery",
                         f"file://{tmp_path}/nothing"]) == 1

    def test_gallery_bad_content_fails_cleanly(self, tmp_env, tmp_path):
        """Malformed index JSON, corrupt archives, traversal archive
        paths, null descriptions, and unregistered schemes all take the
        clean error path (exit 1), never a traceback — the index is
        remote content."""
        g = tmp_path / "g"
        g.mkdir()
        uri = f"file://{g}"
        (g / "index.json").write_text("{not json")
        assert cli_main(["template", "list", "--gallery", uri]) == 1
        (g / "index.json").write_text(json.dumps({"templates": [
            {"name": "x", "archive": "x.tar.gz", "description": None}]}))
        assert cli_main(["template", "list", "--gallery", uri]) == 0
        (g / "x.tar.gz").write_bytes(b"not a gzip")
        tdir = tmp_path / "o"
        assert cli_main(["template", "get", "x", str(tdir),
                         "--gallery", uri]) == 1
        (g / "index.json").write_text(json.dumps({"templates": [
            {"name": "x", "archive": "../outside.tar.gz"}]}))
        assert cli_main(["template", "get", "x", str(tdir),
                         "--gallery", uri]) == 1
        assert cli_main(["template", "list", "--gallery",
                         "gs://nope/x"]) == 1

    def test_gallery_rejected_archive_writes_nothing(self, tmp_env,
                                                     tmp_path):
        """A rejected archive must not leave a partial engine directory:
        valid files followed by an unsafe member extract nothing."""
        import io
        import tarfile
        g = tmp_path / "g"
        g.mkdir()
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tf:
            data = b'{"engineFactory": "recommendation"}'
            ti = tarfile.TarInfo("engine.json")
            ti.size = len(data)
            tf.addfile(ti, io.BytesIO(data))
            bad = tarfile.TarInfo("link")
            bad.type = tarfile.SYMTYPE
            bad.linkname = "/etc/passwd"
            tf.addfile(bad)
        (g / "t.tar.gz").write_bytes(buf.getvalue())
        (g / "index.json").write_text(json.dumps({"templates": [
            {"name": "t", "archive": "t.tar.gz"}]}))
        tdir = tmp_path / "out"
        assert cli_main(["template", "get", "t", str(tdir),
                         "--gallery", f"file://{g}"]) == 1
        assert not (tdir / "engine.json").exists()


class TestServersVerb:
    def test_probes_live_and_down_ports(self, tmp_env, capsys):
        """pio servers reports UP for a listening service and down for
        the rest; exit 0 when anything is live, 1 when nothing is."""
        from predictionio_tpu.data.api.event_server import (
            EventServer, EventServerConfig)
        s = EventServer(EventServerConfig(ip="127.0.0.1", port=0))
        s.start()
        try:
            assert cli_main(["servers", "--event-server-port",
                             str(s.config.port),
                             "--engine-port", "1",
                             "--dashboard-port", "1",
                             "--admin-port", "1"]) == 0
            out = capsys.readouterr().out
            assert "eventserver" in out and "UP" in out
            assert out.count("down") == 3
        finally:
            s.stop()
        assert cli_main(["servers", "--event-server-port", "1",
                         "--engine-port", "1", "--dashboard-port", "1",
                         "--admin-port", "1"]) == 1


class TestDashboard:
    def test_lists_evaluations(self, tmp_env):
        from predictionio_tpu.tools.dashboard import (Dashboard,
                                                      DashboardConfig)
        import datetime as dt
        from predictionio_tpu.data.storage.base import EvaluationInstance
        dao = Storage.get_meta_data_evaluation_instances()
        iid = dao.insert(EvaluationInstance(
            status="EVALCOMPLETED", evaluation_class="MyEval",
            evaluator_results="score: 0.9",
            evaluator_results_html="<html>ok</html>",
            evaluator_results_json='{"score": 0.9}'))
        d = Dashboard(DashboardConfig(ip="127.0.0.1", port=0)).start()
        try:
            p = d.config.port
            status, page = call(p, "GET", "/")
            assert status == 200 and "MyEval" in page
            status, txt = call(
                p, "GET", f"/engine_instances/{iid}/evaluator_results.txt")
            assert txt == "score: 0.9"
            status, j = call(
                p, "GET", f"/engine_instances/{iid}/evaluator_results.json")
            assert j == {"score": 0.9}
            status, _ = call(
                p, "GET", "/engine_instances/nope/evaluator_results.txt")
            assert status == 404
        finally:
            d.stop()


class TestAdminServer:
    def test_app_rest(self, tmp_env):
        from predictionio_tpu.tools.admin import (AdminServer,
                                                  AdminServerConfig)
        s = AdminServer(AdminServerConfig(ip="127.0.0.1", port=0)).start()
        try:
            p = s.config.port
            status, body = call(p, "GET", "/")
            assert body == {"status": "alive"}
            status, body = call(p, "POST", "/cmd/app", {"name": "adminapp"})
            assert status == 200 and body["key"]
            status, body = call(p, "POST", "/cmd/app", {"name": "adminapp"})
            assert status == 409
            status, body = call(p, "GET", "/cmd/app")
            assert [a["name"] for a in body["apps"]] == ["adminapp"]
            status, body = call(p, "DELETE", "/cmd/app/adminapp/data")
            assert status == 200
            status, body = call(p, "DELETE", "/cmd/app/adminapp")
            assert status == 200
            status, body = call(p, "GET", "/cmd/app")
            assert body["apps"] == []
        finally:
            s.stop()


class TestSignalShutdown:
    @pytest.mark.timeout(120)
    def test_eventserver_sigterm_stops_cleanly(self, tmp_path):
        """SIGTERM (systemd/k8s stop) must shut the foreground server
        down cleanly — rc 0 and the shutdown message — not kill it
        mid-request with the port still latched."""
        import os
        import signal
        import socket
        import subprocess
        import sys
        import time
        import urllib.request

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = dict(os.environ, PIO_FS_BASEDIR=str(tmp_path / "store"),
                   JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(repo, "bin", "pio"),
             "eventserver", "--ip", "127.0.0.1", "--port", str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        try:
            deadline = time.time() + 60
            while True:
                try:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/", timeout=2).read()
                    break
                except Exception:
                    if time.time() > deadline:
                        raise RuntimeError("event server never came up")
                    if proc.poll() is not None:
                        raise AssertionError(
                            proc.communicate()[0].decode()[-2000:])
                    time.sleep(0.3)
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                out, _ = proc.communicate()
        assert proc.returncode == 0, out.decode()[-2000:]
        assert "shutting down" in out.decode()


class TestStopLatch:
    def test_http_stop_before_start_is_latched(self):
        """A stop() that lands before the socket exists (SIGTERM during
        the bind-retry window) must win: start() honors the latch at
        bind time instead of serving as a zombie."""
        from predictionio_tpu.utils.http import HttpServer, Router

        s = HttpServer(Router(), "127.0.0.1", 0)
        s.stop()                       # latched pre-bind
        s.start(background=True)
        assert s._httpd is None        # torn down the moment it bound
        # and the port is actually closed (resolved port recorded)
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{s.port}/", timeout=2)

    def test_http_server_is_restartable(self):
        """stop() of a live server consumes the latch (round-4 advisor:
        it used to latch permanently, so a stopped instance could never
        start again — start() tore down immediately after bind)."""
        from predictionio_tpu.utils.http import (HttpServer, Response,
                                                 Router)
        r = Router()
        r.add("GET", "/ping", lambda req: Response(200, {"ok": True}))
        s = HttpServer(r, "127.0.0.1", 0)
        for _ in range(2):
            s.start(background=True)
            try:
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{s.port}/ping", timeout=5).read()
                assert b"ok" in body
            finally:
                s.stop()

    def test_http_normal_lifecycle_unaffected(self):
        from predictionio_tpu.utils.http import (HttpServer, Response,
                                                 Router)
        r = Router()
        r.add("GET", "/ping", lambda req: Response(200, {"ok": True}))
        s = HttpServer(r, "127.0.0.1", 0)
        s.start(background=True)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{s.port}/ping", timeout=5).read()
            assert b"ok" in body
        finally:
            s.stop()


class TestHeaders:
    """Case-insensitive header mapping invariants (RFC 9110 §5.1) —
    every access path must fold the probe key, including mutation and
    copying, so a future handler editing req.headers can't end up with
    a mapping that passes reads and fails writes."""

    def test_reads_fold_case(self):
        from predictionio_tpu.utils.http import Headers
        h = Headers({"Authorization": "Basic x", "TE": "trailers"})
        assert h.get("authorization") == "Basic x"
        assert h["te"] == "trailers"
        assert "AUTHORIZATION" in h
        assert Headers([("A", 1)]).get("a") == 1  # pair-iterable form

    def test_mutation_and_copy_preserve_invariant(self):
        from predictionio_tpu.utils.http import Headers
        h = Headers({"Authorization": "Basic x"})
        assert h.pop("AUTHORIZATION") == "Basic x"
        assert "authorization" not in h
        h["X-Foo"] = "y"
        assert h.get("x-foo") == "y"
        h.update({"Content-Type": "a"}, Accept="b")
        assert h["content-type"] == "a" and h.get("ACCEPT") == "b"
        c = h.copy()
        assert isinstance(c, Headers) and c.get("X-FOO") == "y"
        del h["x-foo"]
        assert "X-Foo" not in h
        assert h.setdefault("Vary", "z") == "z" and h.get("vary") == "z"
