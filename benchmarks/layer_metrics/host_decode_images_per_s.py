"""Images a second the host's decode workers gave while they worked, over the
window: the rise of the program's ``faa_decode_images_total`` over that of
``faa_decode_seconds_total`` (every decoder's) between the window's two ends
(``programs/train_hostfed.py::OneBeatADispatch.feed_over_the_window``, handed over in
``Observed.work``): what the host could give, beside ``train_images_per_s``, what the
device took.  The host bounds the cell where the two meet.  A program from before the
counters (PR 32) has nothing to read."""

META = {"layer": "epoch_driver_data_feed", "unit": "images/s",
        "source": "program_counter", "moves": "train_images_per_s"}


def read(obs):
    return obs.work.get("decode_images_per_s")
