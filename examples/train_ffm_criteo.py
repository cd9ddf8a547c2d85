"""libffm's Criteo job from a Criteo-format click log, on the normal path.

The Criteo Display Advertising Challenge's ``train.txt`` is tab-separated:
a 0/1 label, 13 integer cells (I1..I13) and 26 categorical cells of 8 hex
digits (C1..C26), any of them possibly empty. The CSV parser hashes every
cell to one of ``hash_bins`` table rows while it scans (docs/data.md,
"Hashed cells"; an empty cell is a value of its column), ``DeviceIter``
puts a row's 39 ids as one int32 plane, and ``FFMLearner(layout="dense")``
reads column ``c`` as field ``c`` of one shared id space: no conversion
pass to ``field:id:1`` text.

Run (single host, any JAX backend):
    python examples/train_ffm_criteo.py [train.txt] [hash_bins]

Without a path it writes a small log of the same form.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

COLUMNS = 39      # 13 integer and 26 categorical cells


def synthesize(path: str, rows: int = 8192) -> None:
    """A log whose label follows a pair of its cells, empty cells and all."""
    import numpy as np

    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for _ in range(rows):
            ints = rng.integers(-2, 40, 13)
            cats = rng.integers(0, 50, 26)
            label = int(cats[0] % 2 == cats[1] % 2)   # a pair of cells decides
            cells = [str(v) for v in ints] + [f"{v * 2654435761 % 2**32:08x}"
                                              for v in cats]
            for c in np.flatnonzero(rng.random(COLUMNS) < 0.1):
                if c not in (13, 14):
                    cells[c] = ""
            f.write(f"{label}\t" + "\t".join(cells) + "\n")


def main() -> None:
    from dmlc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import numpy as np

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter
    from dmlc_tpu.models import FFMLearner

    if len(sys.argv) > 1:
        path = sys.argv[1]
        bins = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
        batch = 16_384
    else:
        path, bins, batch = "/tmp/dmlc_tpu_example_criteo.txt", 4_096, 256
        synthesize(path)
    uri = (f"{path}?format=csv&label_column=0&delimiter=\t&dtype=int32"
           f"&hash_bins={bins}")
    it = DeviceIter(create_parser(uri), num_col=COLUMNS, batch_size=batch,
                    layout="dense", x_dtype="int32")
    model = FFMLearner(num_col=bins, num_fields=COLUMNS, layout="dense",
                       column_offsets=np.zeros(COLUMNS, np.int32))
    for epoch in range(5):
        loss, steps = model.fit_epoch(it)
        print(f"epoch {epoch}: mean loss {loss:.4f} over {steps} batches")
    stats = it.stats()
    print(f"cells hashed: {stats['csv_cells']['hashed']}, of them empty: "
          f"{stats['csv_empty_cells']}; accuracy "
          f"{model.accuracy(it):.3f}")
    it.close()


if __name__ == "__main__":
    main()
