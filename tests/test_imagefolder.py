"""Raw-format ingestion: ImageFolder tree, hdf5 streaming, converters,
fetch registry."""

import os
import subprocess
import sys
import tarfile

import numpy as np
import pytest

from fedml_tpu.data.imagefolder import (Hdf5ImageNetSource, decode_image,
                                        load_partition_data_imagenet_hdf5,
                                        load_partition_data_imagenet_tree,
                                        scan_image_tree)

PIL = pytest.importorskip("PIL")
from PIL import Image  # noqa: E402


N_CLASSES, PER_CLASS, HW = 4, 6, 12


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """Tiny ImageFolder tree: 4 wnid classes × 6 train / 2 val images,
    each image a solid color encoding (class, index)."""
    root = tmp_path_factory.mktemp("ilsvrc")
    rng = np.random.RandomState(0)
    for split, per in (("train", PER_CLASS), ("val", 2)):
        for c in range(N_CLASSES):
            d = root / split / f"n{c:08d}"
            d.mkdir(parents=True)
            for i in range(per):
                arr = np.full((16, 20, 3), 40 * c + 5 * i, np.uint8)
                arr += rng.randint(0, 3, arr.shape).astype(np.uint8)
                Image.fromarray(arr).save(d / f"img_{i}.png")
    return str(root)


class TestScan:
    def test_class_major_order_and_ranges(self, tree):
        samples, counts, net_map = scan_image_tree(
            os.path.join(tree, "train"))
        assert len(samples) == N_CLASSES * PER_CLASS
        assert counts == {c: PER_CLASS for c in range(N_CLASSES)}
        for c in range(N_CLASSES):
            b, e = net_map[c]
            assert e - b == PER_CLASS
            assert all(lbl == c for _, lbl in samples[b:e])

    def test_empty_tree_raises(self, tmp_path):
        (tmp_path / "empty_class").mkdir()
        with pytest.raises(RuntimeError, match="0 images"):
            scan_image_tree(str(tmp_path))


class TestDecode:
    def test_shape_crop_and_normalization(self, tree):
        samples, _, _ = scan_image_tree(os.path.join(tree, "train"))
        path = samples[0][0]
        raw = decode_image(path, 8, normalize=False)
        assert raw.shape == (8, 8, 3)
        assert 0.0 <= raw.min() and raw.max() <= 1.0
        norm = decode_image(path, 8, normalize=True)
        # normalize subtracts imagenet mean/std — pixel 0.x maps well below
        assert not np.allclose(raw, norm)

    def test_upscales_small_images(self, tmp_path):
        p = tmp_path / "small.png"
        Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(p)
        assert decode_image(str(p), 8, normalize=False).shape == (8, 8, 3)


class TestTreeFederation:
    def test_by_class_partition(self, tree):
        ds = load_partition_data_imagenet_tree(tree, client_number=2,
                                               image_size=8,
                                               normalize=False)
        assert ds.client_num == 2
        assert ds.class_num == N_CLASSES
        # 2 clients × 2 classes each, class-major
        for cid in range(2):
            y = ds.train_data_local_dict[cid][1]
            assert set(np.unique(y)) == {2 * cid, 2 * cid + 1}
            assert len(y) == 2 * PER_CLASS
        assert ds.test_data_num == N_CLASSES * 2

    def test_indivisible_client_count_raises(self, tree):
        with pytest.raises(ValueError, match="divide"):
            load_partition_data_imagenet_tree(tree, client_number=3,
                                              image_size=8)

    def test_registry_dispatch(self, tree):
        from fedml_tpu.data.registry import load_data

        ds = load_data("ILSVRC2012", tree, client_num_in_total=4,
                       image_size=8)
        assert ds.client_num == 4


class TestHdf5:
    @pytest.fixture(scope="class")
    def pack(self, tree, tmp_path_factory):
        out = str(tmp_path_factory.mktemp("pack") / "imagenet.h5")
        from fedml_tpu.data.convert import convert_imagenet_tree_h5
        convert_imagenet_tree_h5(tree, out, image_size=8, chunk=5)
        return out

    def test_streaming_reader(self, pack):
        src = Hdf5ImageNetSource(pack)
        assert len(src) == N_CLASSES * PER_CLASS
        assert src.n_images("val") == N_CLASSES * 2
        # unsorted gather preserves request order
        got = src.read("train", [7, 0, 3])
        direct = np.stack([src.read("train", [i])[0] for i in (7, 0, 3)])
        np.testing.assert_array_equal(got, direct)
        batches = list(src.iter_batches("train", batch_size=10))
        assert [len(b[1]) for b in batches] == [10, 10, 4]
        src.close()

    def test_hdf5_federation_matches_tree(self, tree, pack):
        ds_tree = load_partition_data_imagenet_tree(tree, client_number=4,
                                                    image_size=8,
                                                    normalize=False)
        ds_h5 = load_partition_data_imagenet_hdf5(pack, client_number=4)
        assert ds_h5.client_num == ds_tree.client_num
        for cid in range(4):
            np.testing.assert_allclose(
                ds_h5.train_data_local_dict[cid][0],
                ds_tree.train_data_local_dict[cid][0], atol=1e-6)
            np.testing.assert_array_equal(
                ds_h5.train_data_local_dict[cid][1],
                ds_tree.train_data_local_dict[cid][1])


class TestLandmarksConverter:
    def test_convert_then_load(self, tmp_path):
        from fedml_tpu.data.convert import convert_landmarks
        from fedml_tpu.data.images import load_partition_data_landmarks

        images_dir = tmp_path / "images"
        images_dir.mkdir()
        csv_path = tmp_path / "federated_train.csv"
        rows = ["user_id,image_id,class"]
        for u in range(3):
            for i in range(4):
                image_id = f"img{u}_{i}"
                rows.append(f"user{u},{image_id},{u}")
                Image.fromarray(np.full((10, 10, 3), 30 * u + i,
                                        np.uint8)).save(
                    images_dir / f"{image_id}.jpg")
        csv_path.write_text("\n".join(rows) + "\n")

        out_dir = tmp_path / "out"
        convert_landmarks(str(images_dir), str(csv_path), str(out_dir),
                          image_size=8)
        # the converted pair feeds the existing landmarks loader
        import shutil
        shutil.copy(csv_path, out_dir / "federated_train.csv")
        ds = load_partition_data_landmarks(str(out_dir),
                                           "federated_train.csv",
                                           class_num=3)
        assert ds.client_num == 3
        for cid in range(3):
            x, y = ds.train_data_local_dict[cid]
            assert x.shape == (4, 8, 8, 3)
            assert set(np.unique(y)) == {cid}


class TestFetch:
    def test_registry_covers_reference_scripts(self):
        from fedml_tpu.data.fetch import REGISTRY

        for name in ("femnist", "fed_cifar100", "fed_shakespeare",
                     "stackoverflow", "cifar10", "cifar100", "landmarks"):
            assert name in REGISTRY
            assert all(s.url.startswith(("http://", "https://"))
                       for s in REGISTRY[name].sources)

    def test_fetch_from_file_mirror_and_extract(self, tmp_path):
        from fedml_tpu.data.fetch import Source, fetch_source

        # build a local "mirror" holding the expected filename
        mirror = tmp_path / "mirror"
        mirror.mkdir()
        payload = tmp_path / "inner.txt"
        payload.write_text("federated!")
        with tarfile.open(mirror / "fed_cifar100.tar.bz2", "w:bz2") as tf:
            tf.add(payload, arcname="fed_cifar100/inner.txt")

        out = tmp_path / "out"
        src = Source("https://fedml.s3-us-west-1.amazonaws.com/"
                     "fed_cifar100.tar.bz2")
        path = fetch_source(src, str(out), base_url=mirror.as_uri())
        assert os.path.exists(path)
        assert (out / "fed_cifar100" / "inner.txt").read_text() == \
            "federated!"

    def test_failed_download_leaves_no_partial(self, tmp_path):
        from fedml_tpu.data.fetch import Source, fetch_source

        src = Source("file:///nonexistent/nowhere.tar.bz2")
        with pytest.raises(RuntimeError, match="manually"):
            fetch_source(src, str(tmp_path))
        assert list(tmp_path.iterdir()) == []

    def test_cli_list(self):
        out = subprocess.run(
            [sys.executable, "-m", "fedml_tpu.data.fetch", "--list"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0
        assert "fed_cifar100" in out.stdout
