"""Helpers the readers share: the program's span records of one run."""


def spans(obs: dict, name: str, **attrs) -> list:
    return [s for s in obs.get("spans") or ()
            if s.get("kind") == "span" and s.get("name") == name
            and all(s.get(k) == v for k, v in attrs.items())]


def median(xs: list):
    xs = sorted(xs)
    if not xs:
        return None
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])
