"""The share of the window's splits that are categorical, in percent, from
the program's counter ``xtpu_cat_splits_total{kind="partition"|"onehot"|
"numeric"}`` (counted where the grown trees are pulled to the host). None
where the program has no such counter or the window grew no split."""


def read(facts):
    splits = (facts.get('cat') or {}).get('splits') or {}
    total = sum(splits.values())
    if not total:
        return None
    return 100.0 * (splits.get('partition', 0)
                    + splits.get('onehot', 0)) / total
