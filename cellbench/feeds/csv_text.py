"""Cold feed of a delimited table: the text is split, parsed and converted
in every epoch, as ``feeds/text.py`` does, with the CSV parser's own
arguments (``label_column``, ``delimiter``, ``dtype``: csv_parser.h) on the
URI. They are the configuration's, and reach this feed through the
adapter's ``device_iter_kwargs()["parser_args"]``, which is taken out
before ``DeviceIter`` is built."""

from __future__ import annotations

from cellbench.feeds.text import served  # noqa: F401 - the same rule


def open_feed(uri: str, work_dir: str, iter_kwargs: dict, params: dict):
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.device import DeviceIter

    kwargs = dict(iter_kwargs)
    args = "".join(f"&{k}={v}" for k, v in kwargs.pop("parser_args").items())
    return DeviceIter(create_parser(uri + args), **kwargs)
