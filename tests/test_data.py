"""Data layer tests — the L2 parity surface (SURVEY.md §1-L2)."""

import numpy as np
import pandas as pd
import pytest

from tpu_air import data as tad
from tpu_air.data import (
    ActorPoolStrategy,
    BatchMapper,
    Chain,
    MinMaxScaler,
    Normalizer,
    PowerTransformer,
)


@pytest.fixture
def taxi_like(air):
    rng = np.random.default_rng(0)
    return tad.from_pandas(
        pd.DataFrame(
            {
                "trip_distance": rng.uniform(0, 30, 200),
                "trip_duration": rng.uniform(60, 3600, 200),
                "passenger_count": rng.integers(1, 6, 200),
            }
        )
    ).repartition(5)


def test_from_items_dicts(air):
    ds = tad.from_items([{"a": i, "b": 2 * i} for i in range(10)])
    assert ds.count() == 10
    assert sorted(ds.columns()) == ["a", "b"]


def test_from_items_objects(air):
    ds = tad.from_items(["x", "y", "z"])
    assert ds.take(2) == [{"item": "x"}, {"item": "y"}]


def test_range_limit_take(air):
    ds = tad.range(100).limit(7)
    assert ds.count() == 7
    assert [r["id"] for r in ds.take(3)] == [0, 1, 2]


def test_repartition(air):
    ds = tad.range(50).repartition(5)
    assert ds.num_blocks() == 5
    assert ds.count() == 50


def test_map_batches_pandas_parallel_tasks(air):
    ds = tad.range(40)

    def double(df: pd.DataFrame) -> pd.DataFrame:
        df = df.copy()
        df["id"] = df["id"] * 2
        return df

    out = ds.map_batches(double, batch_format="pandas")
    assert sorted(r["id"] for r in out.take_all()) == [2 * i for i in range(40)]


def test_map_batches_numpy_format(air):
    ds = tad.range(16)

    def sq(batch):
        return {"id": batch["id"] ** 2}

    out = ds.map_batches(sq, batch_format="numpy")
    assert sorted(r["id"] for r in out.take_all()) == [i * i for i in range(16)]


def test_map_batches_batch_size_respected(air):
    ds = tad.from_pandas(pd.DataFrame({"x": np.arange(100)}))
    sizes = []

    def record(df):
        sizes.append(len(df))
        return df

    # runs in-process? no — tasks; sizes list won't propagate back. Use a
    # column trick instead: tag each row with its batch size.
    def tag(df):
        df = df.copy()
        df["bs"] = len(df)
        return df

    out = ds.map_batches(tag, batch_size=32, batch_format="pandas")
    bs = [r["bs"] for r in out.take_all()]
    assert max(bs) <= 32


def test_map_batches_actor_pool_callable_class(air):
    """The BatchPredictor architecture: callable class constructed once per
    actor (Scaling_batch_inference.ipynb:cc-4)."""

    class AddOffset:
        def __init__(self, offset):
            self.offset = offset

        def __call__(self, df):
            df = df.copy()
            df["id"] = df["id"] + self.offset
            return df

    ds = tad.range(20).repartition(4)
    out = ds.map_batches(
        AddOffset,
        compute=ActorPoolStrategy(size=2),
        fn_constructor_args=(100,),
        batch_format="pandas",
    )
    assert sorted(r["id"] for r in out.take_all()) == [100 + i for i in range(20)]


def test_map_filter_drop_select_add(air):
    ds = tad.from_items([{"a": i, "b": i * 10} for i in range(10)])
    assert ds.map(lambda r: {"c": r["a"] + 1}).take(2) == [{"c": 1}, {"c": 2}]
    assert ds.filter(lambda r: r["a"] % 2 == 0).count() == 5
    assert tad.Dataset.columns(ds.drop_columns(["b"])) == ["a"]
    assert ds.select_columns(["b"]).columns() == ["b"]
    ds2 = ds.add_column("d", lambda df: df["a"] * df["b"])
    assert ds2.take(2)[1]["d"] == 10


def test_train_test_split(air):
    tr, te = tad.range(100).train_test_split(0.2, shuffle=True, seed=57)
    assert tr.count() == 80 and te.count() == 20
    all_ids = sorted(
        [r["id"] for r in tr.take_all()] + [r["id"] for r in te.take_all()]
    )
    assert all_ids == list(range(100))


def test_split_shards(air):
    shards = tad.range(64).split(4)
    assert len(shards) == 4
    assert all(s.count() == 16 for s in shards)


def test_groupby_mean(air):
    ds = tad.from_items([{"k": i % 2, "v": float(i)} for i in range(10)])
    out = ds.groupby("k").mean("v").to_pandas().sort_values("k")
    assert list(out["mean(v)"]) == [4.0, 5.0]


def test_sort_union_zip(air):
    ds = tad.from_items([{"a": i} for i in [3, 1, 2]])
    assert [r["a"] for r in ds.sort("a").take_all()] == [1, 2, 3]
    assert ds.union(ds).count() == 6
    z = ds.zip(tad.from_items([{"b": i} for i in range(3)]))
    assert sorted(z.columns()) == ["a", "b"]


def test_iter_batches_exact_sizes(air):
    ds = tad.range(25).repartition(3)
    batches = list(ds.iter_batches(batch_size=10, batch_format="pandas"))
    assert [len(b) for b in batches] == [10, 10, 5]


def test_write_read_parquet_roundtrip(air, tmp_path):
    ds = tad.range(30)
    path = str(tmp_path / "pq")
    ds.write_parquet(path)
    back = tad.read_parquet(path)
    assert back.count() == 30
    assert sorted(r["id"] for r in back.take_all()) == list(range(30))


def test_object_column_blocks(air):
    """Blocks must hold non-Arrow-able values (PIL images in W7)."""

    class Blob:
        def __init__(self, v):
            self.v = v

    ds = tad.from_items([Blob(i) for i in range(4)])
    assert [b.v for b in (r["item"] for r in ds.take_all())] == [0, 1, 2, 3]


# -- preprocessors -----------------------------------------------------------


def test_minmax_scaler_fit_transform(air, taxi_like):
    pp = MinMaxScaler(columns=["trip_distance"])
    out = pp.fit_transform(taxi_like)
    df = out.to_pandas()
    assert df["trip_distance"].min() == pytest.approx(0.0)
    assert df["trip_distance"].max() == pytest.approx(1.0)
    assert pp.check_is_fitted()


def test_fitted_preprocessor_serializes(air, taxi_like):
    """The checkpoint contract: fitted state survives serialization
    (Introduction…ipynb:cc-19)."""
    import cloudpickle

    pp = MinMaxScaler(columns=["trip_distance"])
    pp.fit(taxi_like)
    pp2 = cloudpickle.loads(cloudpickle.dumps(pp))
    assert pp2.stats_ == pp.stats_
    batch = pd.DataFrame({"trip_distance": [0.0, 100.0]})
    out = pp2.transform_batch(batch)
    assert out["trip_distance"].iloc[0] <= 0.0


def test_batch_mapper_pandas(air):
    pp = BatchMapper(lambda df: df.assign(y=df["id"] + 1), batch_format="pandas")
    ds = tad.range(5)
    assert sorted(r["y"] for r in pp.transform(ds).take_all()) == [1, 2, 3, 4, 5]


def test_power_transformer_and_normalizer(air):
    df = pd.DataFrame({"x": [1.0, 4.0, 9.0], "y": [3.0, 4.0, 0.0]})
    pt = PowerTransformer(columns=["x"], power=0.5)
    out = pt.transform_batch(df.copy())
    assert out["x"].iloc[0] == pytest.approx(2 * (2.0**0.5 - 1))
    nz = Normalizer(columns=["x", "y"])
    out = nz.transform_batch(df.copy())
    norms = np.sqrt(out["x"] ** 2 + out["y"] ** 2)
    np.testing.assert_allclose(norms, 1.0)


def test_chain(air, taxi_like):
    chain = Chain(
        MinMaxScaler(columns=["trip_distance"]),
        BatchMapper(lambda df: df.assign(z=df["trip_distance"] * 2)),
    )
    out = chain.fit_transform(taxi_like)
    assert out.to_pandas()["z"].max() == pytest.approx(2.0)


# -- streaming data plane ----------------------------------------------------


def test_shape_ops_never_materialize_on_driver(air, monkeypatch):
    """split/repartition/random_shuffle/sort/groupby/zip/train_test_split
    must run block-wise via tasks: driver-side to_pandas is forbidden
    (Scaling_batch_inference.ipynb:cc-4 'memory management')."""
    import tpu_air.data.dataset as dsmod

    ds = tad.from_items([{"k": i % 3, "v": float(i)} for i in range(100)])
    ds = ds.repartition(5)

    def boom(self, limit=None):
        raise AssertionError("driver materialization (to_pandas) during a shape op")

    monkeypatch.setattr(dsmod.Dataset, "to_pandas", boom)
    out = ds.repartition(3)
    assert out.num_blocks() == 3
    shuffled = ds.random_shuffle(seed=0)
    parts = ds.split(4)
    tr, te = ds.train_test_split(0.2)
    srt = ds.sort("v", descending=True)
    g = ds.groupby("k").mean("v")
    z = ds.zip(ds.select_columns(["v"]))
    monkeypatch.undo()

    assert sum(p.count() for p in parts) <= 100 and all(p.count() == 25 for p in parts)
    assert tr.count() == 80 and te.count() == 20
    vals = srt.to_pandas()["v"].tolist()
    assert vals == sorted(vals, reverse=True)
    assert shuffled.count() == 100
    assert set(shuffled.to_pandas()["v"]) == set(float(i) for i in range(100))
    gdf = g.to_pandas()
    assert set(gdf["k"]) == {0, 1, 2}
    import numpy as np

    expect = {k: np.mean([float(i) for i in range(100) if i % 3 == k]) for k in range(3)}
    for _, row in gdf.iterrows():
        assert abs(row["mean(v)"] - expect[row["k"]]) < 1e-9
    zdf = z.to_pandas()
    assert list(zdf.columns) == ["k", "v", "v_1"] and (zdf["v"] == zdf["v_1"]).all()


def test_groupby_std_and_count(air):
    import numpy as np

    ds = tad.from_items(
        [{"k": i % 2, "v": float(i)} for i in range(50)]
    ).repartition(4)
    std = ds.groupby("k").std("v").to_pandas()
    cnt = ds.groupby("k").count().to_pandas()
    for k in (0, 1):
        vals = [float(i) for i in range(50) if i % 2 == k]
        assert abs(std[std.k == k]["std(v)"].iloc[0] - np.std(vals, ddof=1)) < 1e-9
        assert cnt[cnt.k == k]["count()"].iloc[0] == len(vals)


def test_actor_pool_autoscales_under_backlog(air):
    """min_size=1 pool must grow toward max_size when blocks queue up."""
    from tpu_air.data.dataset import ActorPoolStrategy

    ds = tad.from_items([{"x": i} for i in range(64)]).repartition(16)
    strat = ActorPoolStrategy(min_size=1, max_size=4)

    class Slowish:
        def __call__(self, df):
            import time

            time.sleep(0.05)
            df = df.copy()
            df["y"] = df["x"] * 2
            return df

    out = ds.map_batches(Slowish, compute=strat, batch_size=None)
    assert out.count() == 64
    assert (out.to_pandas()["y"] == out.to_pandas()["x"] * 2).all()
    assert strat.scaled_to == 4, f"pool did not scale: {strat.scaled_to}"
