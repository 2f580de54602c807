"""Feature-store layer tests (reference capabilities: SURVEY.md §2.6).

Golden behaviors mirrored from the reference notebooks:
feature_engineering / feature_exploration / time_travel_python /
training_datasets / feature_validation_python / feature_store_tags.
"""

import numpy as np
import pandas as pd
import pytest

import hops_tpu.featurestore as hsfs
from hops_tpu.featurestore.validation import DataValidationError, Rule


@pytest.fixture
def fs(workspace):
    return hsfs.connection().get_feature_store()


def sales_df():
    return pd.DataFrame({
        "store_id": [1, 2, 3, 4],
        "sales": [10.0, 20.0, 30.0, 40.0],
        "region": ["n", "s", "n", "w"],
    })


def make_fg(fs, name="sales", online=False, **kw):
    fg = fs.create_feature_group(name, version=1, primary_key=["store_id"],
                                 online_enabled=online, **kw)
    fg.save(sales_df())
    return fg


class TestFeatureGroup:
    def test_save_and_read(self, fs):
        fg = make_fg(fs)
        df = fg.read()
        assert len(df) == 4
        assert set(df.columns) == {"store_id", "sales", "region"}

    def test_schema_inferred(self, fs):
        fg = make_fg(fs)
        types = {f.name: f.type for f in fg.features}
        assert types["store_id"] == "bigint"
        assert types["sales"] == "double"
        assert types["region"] == "string"
        assert fg.get_feature("store_id").primary

    def test_get_feature_group_roundtrip(self, fs):
        make_fg(fs)
        fg = fs.get_feature_group("sales", 1)
        assert fg.primary_key == ["store_id"]
        assert len(fg.read()) == 4

    def test_versioning(self, fs):
        make_fg(fs)
        fg2 = fs.create_feature_group("sales", primary_key=["store_id"])
        assert fg2.version == 2
        fg2.save(sales_df())
        assert fs.get_feature_group("sales").version == 2

    def test_upsert_semantics(self, fs):
        """time_travel_python.ipynb:695 — insert() upserts by primary key."""
        fg = make_fg(fs)
        fg.insert(pd.DataFrame({"store_id": [1, 9], "sales": [99.0, 9.0],
                                "region": ["n", "e"]}))
        df = fg.read().set_index("store_id")
        assert len(df) == 5
        assert df.loc[1, "sales"] == 99.0

    def test_delete_record(self, fs):
        fg = make_fg(fs)
        fg.commit_delete_record(pd.DataFrame({"store_id": [2]}))
        assert sorted(fg.read()["store_id"]) == [1, 3, 4]

    def test_insert_overwrite(self, fs):
        fg = make_fg(fs)
        fg.insert(pd.DataFrame({"store_id": [7], "sales": [1.0], "region": ["x"]}),
                  overwrite=True)
        assert list(fg.read()["store_id"]) == [7]

    def test_commit_details_and_time_travel(self, fs):
        """time_travel_python.ipynb:432,1222 — commit_details + as_of."""
        fg = make_fg(fs)
        details1 = fg.commit_details()
        assert len(details1) == 1
        first_commit = list(details1)[0]
        assert details1[first_commit]["rowsInserted"] == 4
        fg.insert(pd.DataFrame({"store_id": [1, 9], "sales": [99.0, 9.0],
                                "region": ["n", "e"]}))
        details2 = fg.commit_details()
        assert len(details2) == 2
        last = details2[list(details2)[-1]]
        assert last["rowsUpdated"] == 1 and last["rowsInserted"] == 1
        # read as of the first commit: pre-upsert state
        old = fg.read(wallclock_time=first_commit).set_index("store_id")
        assert len(old) == 4 and old.loc[1, "sales"] == 10.0

    def test_read_changes_incremental(self, fs):
        fg = make_fg(fs)
        c1 = list(fg.commit_details())[0]
        fg.insert(pd.DataFrame({"store_id": [9], "sales": [9.0], "region": ["e"]}))
        c2 = list(fg.commit_details())[-1]
        changes = fg.read_changes(c1, c2)
        assert list(changes["store_id"]) == [9]

    def test_statistics(self, fs):
        fg = make_fg(fs, statistics_config={"enabled": True, "histograms": True,
                                            "correlations": True})
        stats = fg.get_statistics()
        assert stats["row_count"] == 4
        assert stats["features"]["sales"]["mean"] == 25.0
        assert "histogram" in stats["features"]["sales"]
        assert "correlations" in stats

    def test_tags(self, fs):
        """feature_store_tags.ipynb cells 16-28."""
        fg = make_fg(fs)
        fg.add_tag("owner", {"team": "ml", "pii": False})
        assert fg.get_tag("owner")["team"] == "ml"
        assert "owner" in fg.get_tags()
        fg.delete_tag("owner")
        assert fg.get_tag("owner") is None


class TestQuery:
    def test_select_filter(self, fs):
        fg = make_fg(fs)
        df = fg.select(["store_id", "sales"]).filter(fg["sales"] > 15).read()
        assert list(df.columns) == ["store_id", "sales"]
        assert sorted(df["store_id"]) == [2, 3, 4]

    def test_compound_filter(self, fs):
        fg = make_fg(fs)
        df = fg.select_all().filter((fg["sales"] > 15) & (fg["region"] == "n")).read()
        assert list(df["store_id"]) == [3]
        df = fg.select_all().filter((fg["sales"] >= 40) | (fg["region"] == "n")).read()
        assert sorted(df["store_id"]) == [1, 3, 4]

    def test_join_on_shared_pk(self, fs):
        """feature_exploration.ipynb cell 27: default join on shared PK."""
        make_fg(fs)
        fg1 = fs.get_feature_group("sales", 1)
        fg2 = fs.create_feature_group("stores", version=1, primary_key=["store_id"])
        fg2.save(pd.DataFrame({"store_id": [1, 2, 3], "size": [5, 6, 7]}))
        df = fg1.select(["store_id", "sales"]).join(fg2.select(["size"])).read()
        assert len(df) == 3  # inner join drops store 4
        assert set(df.columns) >= {"store_id", "sales", "size"}

    def test_join_types_and_keys(self, fs):
        fg1 = make_fg(fs)
        fg2 = fs.create_feature_group("alt", version=1, primary_key=["sid"])
        fg2.save(pd.DataFrame({"sid": [1, 2], "bonus": [0.1, 0.2]}))
        df = fg1.select_all().join(fg2.select_all(), left_on=["store_id"],
                                   right_on=["sid"], join_type="left").read()
        assert len(df) == 4
        assert df["bonus"].isna().sum() == 2

    def test_query_as_of(self, fs):
        fg = make_fg(fs)
        c1 = list(fg.commit_details())[0]
        fg.insert(pd.DataFrame({"store_id": [1], "sales": [99.0], "region": ["n"]}))
        df = fg.select_all().as_of(c1).read()
        assert df.set_index("store_id").loc[1, "sales"] == 10.0

    def test_query_online_read_executes_against_online_store(self, fs):
        """feature_exploration.ipynb cell 12: query.show(n, online=True)
        reads the online store. Divergence setup: offline-only commits
        land before online is enabled, so online holds a strict subset."""
        fg = make_fg(fs)  # offline-only commit (stores 1-4)
        fg.online_enabled = True
        fg._save_meta()
        fg.insert(pd.DataFrame({"store_id": [5], "sales": [50.0], "region": ["s"]}))

        offline = fg.select(["store_id", "sales"]).filter(fg["sales"] > 15).read()
        online = fg.select(["store_id", "sales"]).filter(fg["sales"] > 15).read(online=True)
        assert sorted(offline["store_id"]) == [2, 3, 4, 5]
        assert sorted(online["store_id"]) == [5]  # offline-only rows absent
        assert list(online.columns) == ["store_id", "sales"]
        assert len(fg.select_all().show(3, online=True)) == 1

    def test_query_online_join_and_as_of_guard(self, fs):
        fg1 = make_fg(fs, online=True)
        fg2 = fs.create_feature_group("stores2", version=1, primary_key=["store_id"],
                                      online_enabled=True)
        fg2.save(pd.DataFrame({"store_id": [1, 2], "size": [5, 6]}))
        q = fg1.select(["store_id", "sales"]).join(fg2.select(["size"]))
        df = q.read(online=True)
        assert sorted(df["store_id"]) == [1, 2]
        with pytest.raises(ValueError, match="as_of"):
            fg1.select_all().as_of("2020-01-01 00:00:00").read(online=True)

    def test_query_dataframe_type(self, fs):
        fg = make_fg(fs)
        as_np = fg.select(["store_id", "sales"]).read(dataframe_type="numpy")
        assert isinstance(as_np, np.ndarray) and as_np.shape == (4, 2)
        as_py = fg.select(["store_id"]).read(dataframe_type="python")
        assert isinstance(as_py, list) and as_py[0] == {"store_id": 1}
        with pytest.raises(ValueError, match="dataframe_type"):
            fg.select_all().read(dataframe_type="spark")

    def test_query_serialization_roundtrip(self, fs):
        fg = make_fg(fs)
        q = fg.select(["store_id", "sales"]).filter(fg["sales"] > 15)
        d = q.to_dict()
        q2 = hsfs.Query.from_dict(fs, {"feature_group": d["feature_group"],
                                       "features": d["features"], "joins": [],
                                       "as_of": None})
        assert len(q2.read()) == 4  # filters don't serialize; base query does

    def test_to_string(self, fs):
        fg = make_fg(fs)
        s = fg.select(["sales"]).to_string()
        assert "SELECT sales FROM sales_1" in s


class TestOnline:
    def test_online_write_and_serving_row(self, fs):
        fg = make_fg(fs, online=True)
        assert fg.get_serving_row({"store_id": 2})["sales"] == 20.0

    def test_online_upsert_latest_wins(self, fs):
        fg = make_fg(fs, online=True)
        fg.insert(pd.DataFrame({"store_id": [2], "sales": [77.0], "region": ["s"]}))
        assert fg.get_serving_row({"store_id": 2})["sales"] == 77.0

    def test_online_read(self, fs):
        fg = make_fg(fs, online=True)
        assert len(fg.read(online=True)) == 4


class TestValidation:
    def test_rules_catalog(self, fs):
        conn = hsfs.connection()
        names = {r["name"] for r in conn.get_rules()}
        assert {"HAS_MIN", "HAS_MAX", "IS_CONTAINED_IN"} <= names
        assert conn.get_rule("HAS_MIN")["name"] == "HAS_MIN"

    def test_expectation_warning(self, fs):
        """feature_validation_python.ipynb:304-311,448."""
        fg = make_fg(fs)
        fs.create_expectation(
            "sales_bounds", features=["sales"],
            rules=[Rule(name="HAS_MIN", level="WARNING", min=15)]).save()
        fg.attach_expectation("sales_bounds")
        report = fg.validate()
        assert report["status"] == "WARNING"  # min sales is 10 < 15
        assert fg.get_validations()

    def test_strict_insert_blocked(self, fs):
        fg = fs.create_feature_group(
            "gated", version=1, primary_key=["store_id"],
            validation_type="STRICT", expectations=["nonneg"])
        fs.create_expectation(
            "nonneg", features=["sales"],
            rules=[Rule(name="HAS_MIN", level="ERROR", min=0)]).save()
        fg.save(sales_df())  # passes
        with pytest.raises(DataValidationError):
            fg.insert(pd.DataFrame({"store_id": [5], "sales": [-1.0], "region": ["x"]}))

    def test_contained_in_and_size(self, fs):
        fg = make_fg(fs)
        fs.create_expectation("shape", features=["region"], rules=[
            Rule(name="IS_CONTAINED_IN", level="ERROR", legal_values=["n", "s", "w"]),
            Rule(name="HAS_SIZE", level="ERROR", min=1, max=100),
        ]).save()
        fg.attach_expectation("shape")
        assert fg.validate()["status"] == "SUCCESS"


class TestTrainingDataset:
    def make_td(self, fs, fmt="parquet", **kw):
        fg = make_fg(fs)
        td = fs.create_training_dataset("tds", version=1, data_format=fmt,
                                        label=["sales"], **kw)
        td.save(fg.select(["store_id", "sales"]))
        return td

    def test_save_and_read(self, fs):
        td = self.make_td(fs)
        df = td.read()
        assert len(df) == 4

    def test_splits(self, fs):
        """training_datasets.ipynb cell 10: fractional splits."""
        fg = make_fg(fs)
        big = pd.DataFrame({"store_id": range(100), "sales": np.arange(100.0),
                            "region": ["n"] * 100})
        fg.insert(big)
        td = fs.create_training_dataset("split_td", version=1,
                                        splits={"train": 0.7, "test": 0.3}, seed=42)
        td.save(fg.select_all())
        train, test = td.read("train"), td.read("test")
        assert len(train) + len(test) >= 100  # 4 original + 96 new upserted
        assert abs(len(train) / (len(train) + len(test)) - 0.7) < 0.05

    def test_petastorm_format_tensor_roundtrip(self, fs):
        """PetastormHelloWorld.ipynb role: tensor columns round-trip with
        dtype+shape via the committed unischema; columns project."""
        td = fs.create_training_dataset("peta", version=1, data_format="petastorm")
        images = [np.arange(12, dtype=np.float32).reshape(3, 4) + i for i in range(10)]
        td.save(pd.DataFrame({"image": pd.Series(images, dtype=object),
                              "label": np.arange(10)}))
        back = td.read()
        assert back["image"][0].shape == (3, 4)
        assert back["image"][0].dtype == np.float32
        np.testing.assert_array_equal(back["image"][7], images[7])
        only_labels = td.read(read_options={"columns": ["label"]})
        assert list(only_labels.columns) == ["label"]

    def test_petastorm_row_group_reader(self, fs):
        from hops_tpu.featurestore import columnar

        td = fs.create_training_dataset("peta2", version=1, data_format="petastorm")
        images = [np.full((2, 2), i, np.float32) for i in range(20)]
        td.save(pd.DataFrame({"image": pd.Series(images, dtype=object),
                              "label": np.arange(20)}))
        # Force small row groups by rewriting the split with the public API
        d = td.dir / "data"
        for p in d.glob("part-*.parquet"):
            p.unlink()
        columnar.write_dataset(
            d, pd.DataFrame({"image": pd.Series(images, dtype=object),
                             "label": np.arange(20)}), row_group_size=5)
        reader = td.row_group_reader(shuffle=True, seed=1)
        assert len(reader) == 4  # 20 rows / 5-row groups
        batches = list(reader)
        assert all(b["image"].shape == (5, 2, 2) for b in batches)
        seen = np.sort(np.concatenate([b["label"] for b in batches]))
        np.testing.assert_array_equal(seen, np.arange(20))
        order1 = [int(b["label"][0]) for b in batches]
        order2 = [int(b["label"][0]) for b in list(reader)]  # next epoch reshuffles
        assert order1 != order2

    def test_delta_format_append_overwrite_and_as_of(self, fs):
        """DeltaOnHops.ipynb role: transactional TD with history."""
        td = fs.create_training_dataset("dl", version=1, data_format="delta")
        td.save(pd.DataFrame({"x": [1, 2]}))
        c1 = list(td.commit_details())[-1]
        td.insert(pd.DataFrame({"x": [3]}), overwrite=False)  # append commit
        assert sorted(td.read()["x"]) == [1, 2, 3]
        td.insert(pd.DataFrame({"x": [9]}), overwrite=True)  # truncating commit
        assert sorted(td.read()["x"]) == [9]
        # time travel: as_of the first commit still sees the old table
        assert sorted(td.read(read_options={"as_of": c1})["x"]) == [1, 2]
        details = td.commit_details()
        assert len(details) == 3
        assert [m.get("truncate", False) for m in details.values()] == [True, False, True]
        # hudi alias maps to the transactional format
        td2 = fs.create_training_dataset("dl2", version=1, data_format="HUDI")
        assert td2.data_format == "delta"

    def test_csv_and_recordio_formats(self, fs):
        for fmt in ("csv", "recordio"):
            fg = fs.get_feature_group("sales") if fmt != "csv" else make_fg(fs)
            td = fs.create_training_dataset(f"td_{fmt}", version=1, data_format=fmt)
            td.save(fg.select_all())
            assert len(td.read()) == 4

    def test_query_replay(self, fs):
        td = self.make_td(fs)
        td2 = fs.get_training_dataset("tds", 1)
        q = td2.query
        assert q is not None
        assert len(q.read()) == 4

    def test_numpy_feeder(self, fs):
        td = self.make_td(fs)
        feeder = td.tf_data(target_name="sales")
        batches = list(feeder.numpy_iterator(batch_size=2, num_epochs=2, seed=1))
        assert len(batches) == 4  # 4 rows / bs 2 * 2 epochs
        x, y = batches[0]
        assert x.shape == (2, 1) and y.shape == (2,)
        assert x.dtype == np.float32

    def test_feeder_infinite_and_transform(self, fs):
        td = self.make_td(fs)
        it = td.tf_data(target_name="sales").numpy_iterator(
            batch_size=2, num_epochs=None,
            transform=lambda x, y: {"image": x, "label": y})
        b = next(it)
        assert set(b) == {"image", "label"}

    def test_feeder_start_step_resumes_exact_stream(self, fs):
        """Preemption resume: start_step=k yields exactly what a fresh
        iterator yields from its k-th batch on — same shuffle order,
        across epoch boundaries (pairs with preemption.run_preemptible)."""
        td = self.make_td(fs)
        feeder = td.tf_data(target_name="sales")
        kw = dict(batch_size=2, num_epochs=3, seed=7)  # 2 steps/epoch
        full = list(feeder.numpy_iterator(**kw))
        assert len(full) == 6
        for k in (1, 2, 3, 5):  # mid-epoch, boundary, into later epochs
            resumed = list(feeder.numpy_iterator(**kw, start_step=k))
            assert len(resumed) == 6 - k
            for (fx, fy), (rx, ry) in zip(full[k:], resumed):
                np.testing.assert_array_equal(fx, rx)
                np.testing.assert_array_equal(fy, ry)

    def test_feeder_process_sharded(self, fs):
        """VERDICT r3 item 6 (single-process leg; the two-process leg is
        tests/test_multihost_integration.py): process_sharded yields
        global jax.Arrays assembled via make_array_from_process_local_data
        and sharded over the mesh; the guard rails reject misuse."""
        import jax
        from hops_tpu.parallel import mesh as mesh_lib

        td = self.make_td(fs)
        mesh = mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4])
        sharding = mesh_lib.batch_sharding(mesh, "data")
        feeder = td.tf_data(target_name="sales")
        batches = list(feeder.numpy_iterator(
            batch_size=4, num_epochs=1, shuffle=False,
            process_sharded=True, sharding=sharding))
        assert len(batches) == 1
        x, y = batches[0]
        assert isinstance(x, jax.Array) and x.shape == (4, 1)
        assert x.sharding.spec == jax.sharding.PartitionSpec("data")
        # Same rows as the plain iterator (1 process -> shard == batch).
        px, py = next(feeder.numpy_iterator(batch_size=4, shuffle=False))
        np.testing.assert_allclose(np.asarray(x), px)
        np.testing.assert_allclose(np.asarray(y), py)

        with pytest.raises(ValueError, match="drop_remainder"):
            next(feeder.numpy_iterator(
                batch_size=4, process_sharded=True, drop_remainder=False))
        with pytest.raises(ValueError, match="process_sharded"):
            next(feeder.numpy_iterator(batch_size=4, sharding=sharding))

    def test_tags(self, fs):
        td = self.make_td(fs)
        td.add_tag("purpose", "unit-test")
        assert td.get_tag("purpose") == "unit-test"


class TestServingVector:
    def test_get_serving_vector(self, fs):
        """feature_vector_model_serving.ipynb:175-196."""
        fg = fs.create_feature_group("olfg", version=1, primary_key=["store_id"],
                                     online_enabled=True)
        fg.save(sales_df())
        td = fs.create_training_dataset("serve_td", version=1, label=["sales"])
        td.save(fg.select(["store_id", "sales", "region"]))
        td.init_prepared_statement()
        assert td.serving_keys == ["store_id"]
        vec = td.get_serving_vector({"store_id": 3})
        # feature order minus label: [store_id, region]
        assert vec == [3, "n"]
        vecs = td.get_serving_vectors([{"store_id": 1}, {"store_id": 2}])
        assert len(vecs) == 2


class TestOnDemandAndSQL:
    def test_sql_over_feature_groups(self, fs):
        make_fg(fs)
        df = fs.sql("SELECT region, SUM(sales) AS total FROM sales GROUP BY region "
                    "ORDER BY total DESC")
        assert df.iloc[0]["region"] in ("n", "w")
        assert df["total"].sum() == 100.0

    def test_sql_version_pinned(self, fs):
        make_fg(fs)
        df = fs.sql("SELECT COUNT(*) AS n FROM sales_1")
        assert df["n"][0] == 4

    def test_on_demand_feature_group(self, fs):
        make_fg(fs)
        odfg = fs.create_on_demand_feature_group(
            "sales_agg", version=1,
            query="SELECT region, SUM(sales) AS total FROM sales GROUP BY region")
        odfg.save()
        assert len(odfg.read()) == 3
        got = fs.get_feature_group("sales_agg", 1)
        assert len(got.read()) == 3

    def test_dbapi_cursor(self, fs):
        make_fg(fs)
        conn = __import__("hops_tpu.sql", fromlist=["connection"]).connection(fs)
        cur = conn.cursor()
        cur.execute("SELECT store_id FROM sales ORDER BY store_id")
        assert [r[0] for r in cur.fetchall()] == [1, 2, 3, 4]


class TestConnectors:
    def test_hopsfs_connector(self, fs, workspace):
        import pandas as pd
        from hops_tpu.runtime import fs as hfs

        p = hfs.project_path("Resources/ext.csv")
        __import__("pathlib").Path(p).parent.mkdir(parents=True, exist_ok=True)
        pd.DataFrame({"a": [1, 2]}).to_csv(p, index=False)
        c = fs.create_storage_connector("local", "HOPSFS", path="Resources")
        got = fs.get_storage_connector("local")
        assert len(got.read(path="ext.csv")) == 2

    def test_s3_connector_read_and_ingest(self, fs, tmp_path):
        """VERDICT r3 item 9: the S3 read path executes against a
        filesystem-mocked bucket (S3-Ingest-to-Feature-Store-basics.ipynb:100
        role) — resolve s3:// URIs, read parquet/csv, ingest into a
        feature group, materialize a training dataset from it."""
        bucket = tmp_path / "demo-bucket"
        (bucket / "trips").mkdir(parents=True)
        df = pd.DataFrame({"trip_id": [1, 2, 3], "fare": [7.5, 12.0, 3.2]})
        df.to_parquet(bucket / "trips" / "part-0.parquet")
        pd.DataFrame({"trip_id": [4], "fare": [9.9]}).to_csv(
            bucket / "extra.csv", index=False)

        fs.create_storage_connector(
            "mybucket", "S3", bucket="demo-bucket", mount_point=str(bucket))
        c = fs.get_storage_connector("mybucket", "S3")

        # Bucket-relative key, full s3:// URI, and directory-of-parts.
        assert len(c.read(path="extra.csv")) == 1
        got = c.read(path="s3://demo-bucket/trips")
        pd.testing.assert_frame_equal(
            got.sort_values("trip_id").reset_index(drop=True), df)
        with pytest.raises(ValueError, match="bound to bucket"):
            c.read(path="s3://other-bucket/trips")
        with pytest.raises(ValueError, match="escapes"):
            c.read(path="s3://demo-bucket/../outside.csv")
        # Absolute keys are bucket-relative, never host paths: the read
        # lands (and fails) under the mount, not at /etc.
        with pytest.raises(FileNotFoundError):
            c.read(path="s3://demo-bucket//etc/hostname.csv")
        # URI reads on a bucket-less connector cannot be validated.
        fs.create_storage_connector("loose", "S3", mount_point=str(bucket))
        with pytest.raises(ValueError, match="no bucket configured"):
            fs.get_storage_connector("loose").read(path="s3://demo-bucket/extra.csv")

        # The notebook's pipeline: S3 bytes -> feature group -> TD.
        fg = fs.create_feature_group("trips", version=1, primary_key=["trip_id"])
        fg.save(c.read(path="s3://demo-bucket/trips"))
        td = fs.create_training_dataset("trips_td", version=1, label=["fare"])
        td.save(fg.select_all())
        assert len(td.read()) == 3

    def test_training_dataset_saved_through_s3_connector(self, fs, tmp_path):
        """training_datasets.ipynb cell 12: a TD materializes into the
        connector's storage, not the workspace; the registry still finds
        it, read/feeder work, and the connector restores on reload."""
        bucket = tmp_path / "td-bucket"
        bucket.mkdir()
        fs.create_storage_connector(
            "tdsink", "S3", bucket="td-bucket", mount_point=str(bucket))
        fg = make_fg(fs)
        td = fs.create_training_dataset(
            "s3td", version=1, label=["sales"],
            storage_connector=fs.get_storage_connector("tdsink"))
        td.save(fg.select(["store_id", "sales"]))
        # Files live under the bucket, not the workspace registry entry.
        assert (bucket / "s3td_1" / "data").exists()
        assert not (td.meta_dir / "data").exists()

        again = fs.get_training_dataset("s3td", 1)
        assert again.storage_connector.name == "tdsink"
        assert len(again.read()) == 4
        x, y = again.tf_data(target_name="sales").numpy_arrays()
        assert x.shape == (4, 1) and y.shape == (4,)

    def test_training_dataset_rejects_sql_connector_sink(self, fs):
        fs.create_storage_connector("wh", "SNOWFLAKE", url="u")
        td = fs.create_training_dataset(
            "whtd", version=1, storage_connector=fs.get_storage_connector("wh"))
        with pytest.raises(ValueError, match="cannot host"):
            td.save(pd.DataFrame({"a": [1]}))

    def test_s3_connector_without_mount_raises(self, fs):
        fs.create_storage_connector("far", "S3", bucket="remote-only")
        with pytest.raises(RuntimeError, match="mount"):
            fs.get_storage_connector("far").read(path="s3://remote-only/x.csv")

    def test_snowflake_options(self, fs):
        fs.create_storage_connector("snow", "SNOWFLAKE", url="u", user="x",
                                    database="db", schema="s", warehouse="w")
        c = fs.get_storage_connector("snow")
        opts = c.snowflake_connector_options()
        assert opts["sfURL"] == "u" and opts["sfDatabase"] == "db"
        with pytest.raises(RuntimeError):
            c.read()

    def test_snowflake_account_url_never_treated_as_local_file(self, fs):
        fs.create_storage_connector(
            "snowreal", "SNOWFLAKE", url="xy123.eu-west-1.snowflakecomputing.com")
        with pytest.raises(RuntimeError, match="driver"):
            fs.get_storage_connector("snowreal").read(query="select 1")

    def test_snowflake_embedded_read_path(self, fs, tmp_path):
        """The warehouse-SQL → on-demand-FG path executes when the
        Snowflake connector points at an embedded database — same
        contract as JDBC/Redshift (snowflake/getting-started.ipynb
        role: warehouse query feeds a feature group)."""
        import sqlite3

        db = tmp_path / "wh.db"
        conn = sqlite3.connect(db)
        conn.execute("create table trips (id int, fare real)")
        conn.executemany("insert into trips values (?, ?)",
                         [(1, 7.5), (2, 11.0), (3, 3.25)])
        conn.commit()
        conn.close()

        fs.create_storage_connector(
            "wh_snow", "SNOWFLAKE", url=f"jdbc:sqlite:{db}",
            user="svc", database="wh", schema="public", warehouse="xs")
        c = fs.get_storage_connector("wh_snow", "SNOWFLAKE")
        df = c.read(query="select id, fare from trips where fare > 5 order by id")
        assert list(df["id"]) == [1, 2]
        ofg = fs.create_on_demand_feature_group(
            name="snow_trips", version=1,
            query="select id, fare from trips order by id",
            storage_connector=c)
        got = ofg.read()
        assert len(got) == 3 and got["fare"].iloc[2] == 3.25

    def test_unknown_connector(self, fs):
        with pytest.raises(KeyError):
            fs.get_storage_connector("nope")


class TestReviewRegressions:
    """Regressions for code-review findings on the featurestore layer."""

    def test_overwrite_purges_online_store(self, fs):
        fg = make_fg(fs, online=True)
        fg.insert(pd.DataFrame({"store_id": [7], "sales": [1.0], "region": ["x"]}),
                  overwrite=True)
        assert fg.get_serving_row({"store_id": 2}) is None
        assert fg.get_serving_row({"store_id": 7})["sales"] == 1.0

    def test_split_never_drops_rows(self, fs):
        fg = make_fg(fs)
        fg.insert(pd.DataFrame({"store_id": range(100, 746),
                                "sales": np.arange(646.0),
                                "region": ["n"] * 646}))
        td = fs.create_training_dataset(
            "rounding_td", version=1,
            splits={"train": 0.25164698, "test": 0.74835302}, seed=3)
        td.save(fg.select_all())
        total = len(td.read("train")) + len(td.read("test"))
        assert total == len(fg.read())

    def test_as_of_int_replay(self, fs):
        fg = make_fg(fs)
        c1 = list(fg.commit_details())[0]
        fg.insert(pd.DataFrame({"store_id": [1], "sales": [99.0], "region": ["n"]}))
        td = fs.create_training_dataset("asof_td", version=1)
        td.save(fg.select_all().as_of(c1))
        replay = fs.get_training_dataset("asof_td", 1).query
        df = replay.read().set_index("store_id")
        assert df.loc[1, "sales"] == 10.0

    def test_strict_fg_can_delete(self, fs):
        fg = fs.create_feature_group("strictdel", version=1,
                                     primary_key=["store_id"],
                                     validation_type="STRICT",
                                     expectations=["del_amt"])
        fs.create_expectation("del_amt", features=["sales"],
                              rules=[Rule(name="HAS_MIN", level="ERROR", min=0)]).save()
        fg.save(sales_df())
        fg.commit_delete_record(pd.DataFrame({"store_id": [1]}))
        assert sorted(fg.read()["store_id"]) == [2, 3, 4]

    def test_filter_on_joined_unselected_column(self, fs):
        """A parent filter referencing a joined group's column must work
        even when that column is not in the joined query's selection."""
        make_fg(fs)
        stores = fs.create_feature_group("stores", version=1, primary_key=["store_id"])
        stores.save(pd.DataFrame({"store_id": [1, 2, 3, 4],
                                  "size": [5, 50, 500, 5000],
                                  "city": ["a", "b", "c", "d"]}))
        fg = fs.get_feature_group("sales")
        q = fg.select_all().join(stores.select(["city"])).filter(stores["size"] > 100)
        df = q.read()
        assert sorted(df["store_id"]) == [3, 4]
        # projection: the execution-only filter column is not in the result
        assert "size" not in df.columns and "city" in df.columns

    def test_result_projected_to_selection(self, fs):
        fg = make_fg(fs)
        df = fg.select(["store_id"]).filter(fg["sales"] > 15).read()
        assert list(df.columns) == ["store_id"]
        assert sorted(df["store_id"]) == [2, 3, 4]

    def test_as_of_does_not_mutate_subquery(self, fs):
        fg = make_fg(fs)
        stores = fs.create_feature_group("stores2", version=1, primary_key=["store_id"])
        stores.save(pd.DataFrame({"store_id": [1, 2, 3, 4], "size": [1, 2, 3, 4]}))
        c1 = list(stores.commit_details())[0]
        sub = stores.select_all()
        fg.select_all().join(sub).as_of(c1).read()
        stores.insert(pd.DataFrame({"store_id": [9], "size": [9]}))
        # an independent read of the shared sub-query must see latest data
        assert 9 in sub.read()["store_id"].values

    def test_keyless_fg_statistics_cover_full_table(self, fs):
        fg = fs.create_feature_group(
            "events", version=1,
            statistics_config={"enabled": True, "histograms": False,
                               "correlations": False})
        fg.save(pd.DataFrame({"v": [1.0, 2.0]}))
        fg.insert(pd.DataFrame({"v": [3.0]}))
        stats = fg.get_statistics()
        assert stats["row_count"] == 3  # full table, not just the last commit

    def test_split_categorical_encoding_consistent(self, fs):
        """String features must encode to the same integers in every split."""
        fg = fs.create_feature_group("cats", version=1, primary_key=["id"])
        rng = np.random.RandomState(0)
        n = 400
        cat = np.array(["aa", "bb", "cc", "dd"])[rng.randint(0, 4, n)]
        # value correlates with category so the mapping is observable
        val = {"aa": 0.0, "bb": 1.0, "cc": 2.0, "dd": 3.0}
        fg.save(pd.DataFrame({"id": range(n), "cat": cat,
                              "y": [val[c] for c in cat]}))
        td = fs.create_training_dataset("cats_td", version=1,
                                        splits={"train": 0.9, "test": 0.1}, seed=1)
        td.save(fg.select(["cat", "y"]))
        xs, ys = {}, {}
        for split in ("train", "test"):
            x, y = td.tf_data(target_name="y", split=split).numpy_arrays()
            xs[split], ys[split] = x, y
        # same category -> same code across splits: code->y must agree
        mapping = {}
        for split in ("train", "test"):
            for code, y in zip(xs[split][:, 0], ys[split]):
                assert mapping.setdefault(code, y) == y


class TestJDBCIngest:
    """Warehouse-SQL ingest (round 3): external sqlite -> on-demand FG ->
    query join -> training dataset (reference: snowflake/getting-started
    + Redshift_pyspark roles)."""

    def _external_db(self, tmp_path):
        import sqlite3

        db = tmp_path / "warehouse.db"
        con = sqlite3.connect(db)
        con.executescript(
            """
            CREATE TABLE orders (store_id INTEGER, amount REAL);
            INSERT INTO orders VALUES (1, 10.0), (1, 5.0), (2, 7.5), (3, 2.5);
            """
        )
        con.commit()
        con.close()
        return db

    def test_jdbc_connector_executes_query(self, fs, workspace, tmp_path):
        from hops_tpu.featurestore import connectors

        db = self._external_db(tmp_path)
        c = connectors.create("wh", "JDBC", connection_string=f"jdbc:sqlite:{db}")
        df = c.read("SELECT store_id, SUM(amount) AS total FROM orders GROUP BY store_id")
        assert list(df["total"]) == [15.0, 7.5, 2.5]
        # registry round-trip keeps it functional
        again = connectors.get("wh", "JDBC")
        assert len(again.read("SELECT * FROM orders")) == 4

    def test_jdbc_network_urls_still_raise(self, fs, workspace):
        from hops_tpu.featurestore import connectors

        c = connectors.create(
            "rs", "REDSHIFT",
            connection_string="jdbc:redshift://cluster:5439/db")
        with pytest.raises(RuntimeError, match="driver"):
            c.read("SELECT 1")

    def test_external_sql_to_on_demand_fg_to_training_dataset(self, fs, workspace, tmp_path):
        from hops_tpu.featurestore import connectors

        db = self._external_db(tmp_path)
        wh = connectors.create("wh2", "JDBC", connection_string=f"jdbc:sqlite:{db}")

        # On-demand FG whose query executes IN the external database.
        odfg = fs.create_on_demand_feature_group(
            "order_totals", version=1,
            query="SELECT store_id, SUM(amount) AS total FROM orders GROUP BY store_id",
            storage_connector=wh)
        odfg.save()
        assert list(odfg.read()["total"]) == [15.0, 7.5, 2.5]

        # Join against a materialized FG and land a training dataset.
        stores = fs.create_feature_group("stores", version=1, primary_key=["store_id"])
        stores.save(pd.DataFrame({"store_id": [1, 2, 3], "region": ["n", "s", "w"]}))
        joined = fs.sql(
            "SELECT s.region, o.total FROM stores s "
            "JOIN order_totals o ON s.store_id = o.store_id")
        td = fs.create_training_dataset("wh_td", version=1)
        td.save(joined)
        out = td.read()
        assert set(out.columns) == {"region", "total"} and len(out) == 3


class TestScalaBuilderErgonomics:
    """The reference's JVM builder call shapes (ComputeFeatures.scala:
    108-115, 312-327), line-for-line in Python (featurestore/builders.py)."""

    def test_feature_group_builder_roundtrip(self, fs):
        from hops_tpu.featurestore.builders import StatisticsConfig, TimeTravelFormat

        fg = (fs.createFeatureGroup()
                .name("games_features")
                .version(1)
                .description("Features of games")
                .timeTravelFormat(TimeTravelFormat.HUDI)
                .primaryKeys(["home_team_id"])
                .partitionKeys(["score"])
                .statisticsConfig(StatisticsConfig(True, True, True))
                .build())
        fg.save(pd.DataFrame({
            "home_team_id": [1, 2], "score": [3, 4], "away_team_id": [5, 6],
        }))
        got = fs.getFeatureGroup("games_features", 1)
        assert got.primary_key == ["home_team_id"]
        assert got.time_travel_format == "COMMIT_LOG"
        assert got.statistics_config.histograms
        assert len(got.read()) == 2

    def test_training_dataset_builder_saves_query(self, fs):
        from hops_tpu.featurestore.builders import DataFormat

        make_fg(fs)
        td = (fs.createTrainingDataset()
                .name("tour_td")
                .version(1)
                .description("tour TD")
                .dataFormat(DataFormat.TFRECORD)
                .build())
        td.save(fs.get_feature_group("sales", 1).select_all())
        assert td.data_format == "tfrecord"
        assert len(td.read()) == 4

    def test_connection_builder(self, fs):
        from hops_tpu.featurestore.builders import HopsworksConnection

        conn = HopsworksConnection.builder.build()
        assert conn.get_feature_store().getName()


class TestTrainingDatasetConnectorRegressions:
    """Review findings on the connector-backed TD data root."""

    def test_delete_with_unresolvable_connector_removes_registry(self, fs):
        fs.create_storage_connector("wh2", "SNOWFLAKE", url="u")
        td = fs.create_training_dataset(
            "whtd2", version=1, storage_connector=fs.get_storage_connector("wh2"))
        td._save_meta()
        assert (td.meta_dir / "metadata.json").exists()
        td.delete()  # must not raise despite the unresolvable data dir
        assert not td.meta_dir.exists()

    def test_resave_preserves_tags(self, fs):
        fg = make_fg(fs)
        td = fs.create_training_dataset("tagged", version=1, label=["sales"])
        td.save(fg.select(["store_id", "sales"]))
        td.add_tag("owner", "ml-team")
        td.insert(fg.select(["store_id", "sales"]))  # re-save path
        assert fs.get_training_dataset("tagged", 1).get_tag("owner") == "ml-team"

    def test_load_with_missing_connector_registry_entry(self, fs, tmp_path):
        """Registry wiped after a connector-backed TD was saved: the TD
        must still load (for inspection) and delete; reads name the
        missing connector."""
        bucket = tmp_path / "gone-bucket"
        bucket.mkdir()
        fs.create_storage_connector(
            "gonesink", "S3", bucket="gone-bucket", mount_point=str(bucket))
        fg = make_fg(fs, name="gsales")
        td = fs.create_training_dataset(
            "gtd", version=1, storage_connector=fs.get_storage_connector("gonesink"))
        td.save(fg.select(["store_id", "sales"]))

        from hops_tpu.featurestore import connectors as conn_mod
        conn_mod._registry_path().write_text("{}")  # registry wiped

        again = fs.get_training_dataset("gtd", 1)
        with pytest.raises(RuntimeError, match="missing from the connector"):
            again.read()
        again.delete()  # must not raise
        assert not again.meta_dir.exists()


class TestBias:
    """Slice/fairness analysis (feature-bias-whatif.ipynb role)."""

    @staticmethod
    def _frame():
        import numpy as np

        # Group A: perfect classifier. Group B: catches half the
        # positives. Known-answer disparities follow.
        n = 100
        y = np.r_[np.ones(50), np.zeros(50), np.ones(50), np.zeros(50)].astype(int)
        yhat = y.copy()
        yhat[100:150] = np.r_[np.ones(25), np.zeros(25)].astype(int)  # B: tpr 0.5
        return pd.DataFrame({
            "group": ["A"] * n + ["B"] * n, "label": y, "pred": yhat,
        })

    def test_slice_metrics_known_answers(self):
        from hops_tpu.featurestore import bias

        m = bias.slice_metrics(self._frame(), "label", "pred", "group")
        a = m[m["group"] == "A"].iloc[0]
        b = m[m["group"] == "B"].iloc[0]
        assert a["accuracy"] == 1.0 and a["tpr"] == 1.0 and a["acceptance_rate"] == 0.5
        assert b["tpr"] == 0.5 and b["accuracy"] == 0.75 and b["acceptance_rate"] == 0.25

    def test_disparity_and_report(self):
        from hops_tpu.featurestore import bias

        rep = bias.bias_report(self._frame(), "label", "pred", "group")
        assert rep["demographic_parity"]["gap"] == pytest.approx(0.25)
        assert rep["demographic_parity"]["max_group"] == "A"
        assert rep["equal_opportunity"]["gap"] == pytest.approx(0.5)
        assert rep["accuracy_gap"]["gap"] == pytest.approx(0.25)

    def test_threshold_binarizes_scores(self):
        import numpy as np
        from hops_tpu.featurestore import bias

        df = pd.DataFrame({
            "g": ["x", "x", "y", "y"], "label": [1, 0, 1, 0],
            "score": [0.9, 0.2, 0.4, 0.1],
        })
        m = bias.slice_metrics(df, "label", "score", "g", threshold=0.5)
        assert m[m["g"] == "x"]["accuracy"].iloc[0] == 1.0
        assert m[m["g"] == "y"]["tpr"].iloc[0] == 0.0  # 0.4 < 0.5 missed

        sweep = bias.threshold_sweep(df, "label", "score", "g",
                                     thresholds=[0.3, 0.5])
        # At 0.3 both positives accepted (tpr gap 0); at 0.5 only x's.
        assert sweep.loc[sweep["threshold"] == 0.3, "overall_accuracy"].iloc[0] == 1.0

    def test_multi_column_slices(self):
        from hops_tpu.featurestore import bias

        df = self._frame()
        df["age"] = (["young"] * 50 + ["old"] * 50) * 2
        m = bias.slice_metrics(df, "label", "pred", ["group", "age"])
        assert len(m) == 4
        d = bias.disparity(m, "tpr")
        # Positives live only in the young slices: A/young tpr=1.0 vs
        # B/young tpr=0.5; the all-negative old slices are NaN-dropped.
        assert d["gap"] == pytest.approx(0.5)
        assert d["max_group"] == ("A", "young")

    def test_non_binary_labels_fail_fast(self):
        """Census-style string labels must be binarized, not silently
        compared against 1 (which would report zero disparity)."""
        from hops_tpu.featurestore import bias

        df = pd.DataFrame({"g": ["A", "B"], "label": ["<=50K", ">50K"],
                           "pred": [0, 1]})
        with pytest.raises(ValueError, match="binarize"):
            bias.slice_metrics(df, "label", "pred", "g")
        df2 = pd.DataFrame({"g": ["A", "B"], "label": [0, 1], "pred": [0.7, 0.4]})
        with pytest.raises(ValueError, match="threshold"):
            bias.slice_metrics(df2, "label", "pred", "g")

    def test_slice_column_name_collision_rejected(self):
        from hops_tpu.featurestore import bias

        df = pd.DataFrame({"count": ["A", "B"], "label": [0, 1], "pred": [0, 1]})
        with pytest.raises(ValueError, match="collide"):
            bias.slice_metrics(df, "label", "pred", "count")


def test_pack_documents_lm_layout():
    """Ragged docs -> (n, seq_len + 1) rows: eos separates documents,
    the stream chunks without interior padding, and the remainder pads
    or drops as asked."""
    from hops_tpu.featurestore.feed import pack_documents

    docs = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10]]
    packed = pack_documents(docs, seq_len=4, eos_id=99, pad_id=0,
                            drop_remainder=False)
    # Stream: 1 2 3 99 4 5 99 6 7 8 9 10 99 -> 13 tokens, rows of 5.
    assert packed.shape == (3, 5)
    assert packed[0].tolist() == [1, 2, 3, 99, 4]
    assert packed[1].tolist() == [5, 99, 6, 7, 8]
    assert packed[2].tolist() == [9, 10, 99, 0, 0]  # padded remainder
    dropped = pack_documents(docs, seq_len=4, eos_id=99)
    assert dropped.shape == (2, 5)

    import pytest

    with pytest.raises(ValueError, match="too short"):
        pack_documents([[1]], seq_len=8, eos_id=99)


def test_prefetch_to_device_keeps_full_depth():
    """After the first yield the pipeline must still hold ``size``
    batches in flight (the refill happens BEFORE the yield), and the
    depth gauge reports it."""
    from hops_tpu.featurestore.feed import prefetch_to_device
    from hops_tpu.telemetry import REGISTRY

    produced = []

    def gen():
        for i in range(6):
            produced.append(i)
            yield np.full((2,), i, np.float32)

    it = prefetch_to_device(gen(), size=3, name="t-prefetch")
    first = next(it)
    assert first[0] == 0
    # 3 on device + the one just handed out -> 4 produced, not 3.
    assert len(produced) == 4
    depth = REGISTRY.gauge("hops_tpu_feed_prefetch_depth", labels=("pipeline",))
    assert depth.value(pipeline="t-prefetch") == 3
    rest = [int(b[0]) for b in it]
    assert rest == [1, 2, 3, 4, 5]
    assert depth.value(pipeline="t-prefetch") == 0
